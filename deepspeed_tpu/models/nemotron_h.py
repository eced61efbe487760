"""Nemotron-H (`model_type` `nemotron_h`, Nemotron 3 Nano): layers that
are each ONE part — a Mamba-2 mixer, grouped attention without
positions, or sigmoid-routed two-matrix experts beside a shared one — in
an order the configuration spells out letter by letter.

The stream is the embedding's row (no scale), float32.  Layer l, with
N(x) = x rsqrt(mean x^2 + eps) w (a plain gain), is
x <- x + part_l(N_l x): one norm and one part, `pattern[l]` says which —
"M", "*" or "E".  Then a final norm and an untied head over the rows of
the vocabulary held.  No bias but the convolution's and the router's
choosing bias.

"M" — Mamba-2 (models/granite_hybrid.py `ssm_mix`) of `ssm_heads` heads
of `ssm_head_dim` over `ssm_groups` groups of `ssm_state` state values:
  [z | xBC | dt] = h W_in      (d_in | d_in + 2 groups x state | heads)
  xBC <- silu(causal depthwise convolution, `ssm_conv` taps, bias)
  [x | B | C] = xBC            (d_in | groups x state | groups x state)
  D_t = softplus(dt + dt_bias),  A = -exp(A_log)            (float32)
  head h, group g = h // (heads / groups), state S_h [head_dim, state]:
  S_h <- exp(D_t A_h) S_h + D_t x_h B_g^T;  y_h = S_h C_g + D_h x_h
  out = W_out G(y * silu(z)), G an RMS norm over each GROUP's d_in /
  groups values apart (gate first, then norm), one gain of d_in.
A prefill chunk scans `ssm_chunk` positions at a time, a decode step is
the recurrence; such a layer keeps, a request, the float32 state and the
convolution's last `ssm_conv - 1` inputs and no cache rows.

"*" — grouped attention (models/cohere2_moe.py): `num_heads` query heads
on `kv_heads` keys and values of `head_dim`, query head n reading K/V
head n // (num_heads / kv_heads), causal softmax at head_dim^-1/2, NO
positions (the Mamba-2 layers carry order).  A token's cache row in such
a layer is its `kv_heads` keys and values; no other layer owns rows.

"E" — experts (moe/dropless.py, models/cohere2_moe.py `routed_ffn`):
s = sigmoid(h W_r) over `num_experts` in float32; the `top_k` with the
largest s + b (b the layer's `select_bias`: it chooses and does not
weigh); weights s_i / sum s_i times `route_scale`; each expert
W_d relu(W_u h)^2 — two matrices, `up` and `down`, no `gate` — among the
`experts_held` this chip holds from `first_expert` on; plus the shared
expert, the same form at `d_shared`, summed.  Such a layer owns neither
rows nor a state.

The serving engine runs the model through `layer_spec()` (the "single"
residual of serving/layers.py `block`); `apply` is the uncached forward
the tests compare with `benchmarks/reference/nemotron_h.py`, which knows
the recurrence only.  Training it, grouped top-k (`n_group` > 1), the
exchange between the chips that share a layer's experts and a mesh are
not built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from .cohere2_moe import attend_grouped, expert_ffn, project_grouped
from .deepseek_v2 import rms_norm_plain
from .evabyte import matmul32
from .granite_hybrid import ssm_mix
from .layer_spec import LayerSpec

PARTS = {"M": "ssm", "*": "attention", "E": "none"}


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072         # rows of the vocabulary held
    max_seq_len: int = 262144
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    d_model: int = 2688
    num_heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_conv: int = 4
    ssm_chunk: int = 128
    d_expert: int = 1856
    d_shared: int = 3712
    num_experts: int = 128           # the router's outputs
    top_k: int = 6
    route_scale: float = 2.5
    experts_held: int = 0            # 0: every expert is held here
    first_expert: int = 0
    norm_eps: float = 1e-5
    # seeded weights only: every matrix N(0, init_std), the choosing
    # bias N(0, bias_std) (NOT zero: a bias let into the weights shows),
    # the taps uniform in +-init_conv; a head forgets over 1 / (A step)
    # tokens, -A = -exp(A_log) uniform in init_a and step =
    # softplus(dt_bias) log-uniform in init_dt
    init_std: float = 0.02
    bias_std: float = 0.01
    init_conv: float = 0.5
    init_a: tuple = (1.0, 16.0)
    init_dt: tuple = (0.001, 0.1)
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(PARTS):
            raise ValueError(f"pattern {self.pattern!r} spells each layer "
                             f"as one of {sorted(PARTS)}")
        if self.num_heads % self.kv_heads or \
                self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"num_heads {self.num_heads} must be a multiple of kv_heads "
                f"({self.kv_heads}) and ssm_heads {self.ssm_heads} of "
                f"ssm_groups ({self.ssm_groups})")
        if self.d_inner % self.ssm_groups or self.ssm_conv < 2:
            raise ValueError("the gated norm takes d_inner / ssm_groups "
                             "values a group, and the convolution has at "
                             "least 2 taps")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} must lie in 1.."
                             f"num_experts ({self.num_experts})")
        if self.experts_held < 0 or self.first_expert < 0 or \
                self.first_expert + self.experts_held > self.num_experts:
            raise ValueError(
                f"a share of the experts is experts_held >= 0 experts from "
                f"first_expert on, inside the router's {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def held(self) -> int:
        """Routed experts whose matrices are here."""
        return self.experts_held or self.num_experts


class NemotronH:
    """Nemotron-H's language model as the serving engine and the tests
    take it: `init` from a key, `apply` (uncached), `layer_spec` (what
    serving builds its programs from)."""

    def __init__(self, config: NemotronHConfig):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        return LayerSpec(
            norm="rmsnorm", positions="none", attention="grouped",
            ffn="routed_experts", head="untied", eps=c.norm_eps,
            residual="single", kv_heads=c.kv_heads,
            layer_mixers=tuple(PARTS[part] for part in c.pattern),
            ssm_heads=c.ssm_heads, ssm_head_dim=c.ssm_head_dim,
            ssm_state=c.ssm_state, ssm_conv=c.ssm_conv,
            ssm_chunk=c.ssm_chunk, ssm_groups=c.ssm_groups,
            top_k=c.top_k, scoring="sigmoid", renormalize=True,
            select_bias=True, route_scale=c.route_scale,
            experts_held=c.experts_held,
            first_expert=c.first_expert).validate()

    def init(self, rng):
        c = self.config
        d, dt, std = c.d_model, c.param_dtype, c.init_std
        H, KV, dh = c.num_heads, c.kv_heads, c.head_dim

        def normal(key, shape, scale=std):
            return (jax.random.normal(key, shape) * scale).astype(dt)

        def uniform(key, lo, hi):
            return jax.random.uniform(key, (c.ssm_heads,), jnp.float32,
                                      lo, hi)

        def mixer(key):
            k = jax.random.split(key, 6)
            step = jnp.exp(uniform(k[3], *map(math.log, c.init_dt)))
            return {"in": normal(k[0], (d, c.d_inner + c.conv_width
                                        + c.ssm_heads)),
                    "conv_w": jax.random.uniform(
                        k[1], (c.conv_width, c.ssm_conv), jnp.float32,
                        -c.init_conv, c.init_conv).astype(dt),
                    "conv_b": normal(k[5], (c.conv_width,)),
                    "A_log": jnp.log(uniform(k[2], *c.init_a)),
                    # the inverse softplus of the step
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "D": jnp.ones((c.ssm_heads,), jnp.float32),
                    "norm": {"scale": jnp.ones((c.d_inner,), dt)},
                    "out": normal(k[4], (c.d_inner, d))}

        def attention(key):
            k = jax.random.split(key, 4)
            return {"q": normal(k[0], (d, H * dh)),
                    "k": normal(k[1], (d, KV * dh)),
                    "v": normal(k[2], (d, KV * dh)),
                    "o": normal(k[3], (H * dh, d))}

        def experts(key):
            k = jax.random.split(key, 6)
            return {"router": normal(k[0], (d, c.num_experts)),
                    "select_bias": jax.random.normal(
                        k[1], (c.num_experts,)) * c.bias_std,
                    "experts": {
                        "up": normal(k[2], (c.held, d, c.d_expert)),
                        "down": normal(k[3], (c.held, c.d_expert, d))},
                    "shared": {"up": normal(k[4], (d, c.d_shared)),
                               "down": normal(k[5], (c.d_shared, d))}}

        part = {"M": ("ssm", mixer), "*": ("attn", attention),
                "E": ("mlp", experts)}

        def block(letter, key):
            name, make = part[letter]
            return {"ln1": {"scale": jnp.ones((d,), dt)}, name: make(key)}

        keys = jax.random.split(rng, c.num_layers + 2)
        return {"wte": normal(keys[0], (c.vocab_size, d)),
                "blocks": [block(letter, k)
                           for letter, k in zip(c.pattern, keys[2:])],
                "ln_f": {"scale": jnp.ones((d,), dt)},
                "lm_head": normal(keys[1], (d, c.vocab_size))}

    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, vocab] float32, no
        cache: every Mamba-2 layer scans the whole sequence from a state
        of zeros."""
        c, spec = self.config, self.layer_spec()
        B, S = tokens.shape
        pad = -S % min(c.ssm_chunk, S)
        x = params["wte"][tokens].astype(jnp.float32)
        pos = jnp.arange(S)
        causal = jnp.broadcast_to(pos[None, :] <= pos[:, None], (B, S, S))
        positions = jnp.broadcast_to(pos, (B, S))
        for letter, p in zip(c.pattern, params["blocks"]):
            h = rms_norm_plain(x, p["ln1"], c.norm_eps)
            if letter == "M":
                part, _, _ = ssm_mix(
                    spec, p["ssm"], jnp.pad(h, ((0, 0), (0, pad), (0, 0))),
                    jnp.zeros((B, c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                              jnp.float32),
                    jnp.zeros((B, c.ssm_conv - 1, c.conv_width),
                              c.param_dtype),
                    jnp.full((B,), S, jnp.int32))
                part = part[:, :S]
            elif letter == "*":
                q, k, v = project_grouped(c, p["attn"], h, positions, False,
                                          c.param_dtype)
                part = matmul32(attend_grouped(q, k, v, causal),
                                p["attn"]["o"])
            else:
                part = expert_ffn(spec, c, p["mlp"], h)[0]
            x = x + part
        h = rms_norm_plain(x, params["ln_f"], c.norm_eps)
        return matmul32(h, params["lm_head"])

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
