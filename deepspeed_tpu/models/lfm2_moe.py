"""LFM2-MoE (`model_type` `lfm2_moe`, LFM2-24B-A2B): gated
short-convolution mixers whose whole memory is their last inputs, beside
a few grouped-attention layers with normed and rotated q and k, and
sigmoid-routed SiLU-gated experts behind leading dense layers.

The stream is the embedding's row (no scale), float32.  With
N(x) = x rsqrt(mean x^2 + eps) w (a plain gain), layer l is pre-norm
and sequential: x <- x + mixer_l(N x), x <- x + ffn_l(N x).  Then a
final norm and the tied head over the rows of the vocabulary held.  No
bias anywhere but the router's choosing bias.

`layer_types[l]` says which mixer layer l has.

"conv" — the gated short convolution (`conv_mix`), for the token at t
with h_t the normed stream, D the model's width, K = `conv_taps`:
  [B_t | C_t | u_t] = h_t W_in                      (D | D | D)
  g_t = B_t * u_t
  c_t = sum_{j<K} w[:, j] g_{t-(K-1)+j}             (causal, depthwise,
                                                     zeros before the start)
  out_t = W_out (C_t * c_t)
No activation, no recurrence, no positions.  Such a layer keeps, for a
request, g's last K - 1 rows and NOTHING else (`LayerSpec.state_shapes`:
one array [K - 1, D] a slot at the cache's dtype): `conv_mix` takes them
and hands them back moved on by the call's valid positions — a prefill
chunk convolves from them and leaves its last K - 1 VALID g behind, a
decode step moves a running slot's on by one and hands any other slot's
back as it found them.

"full_attention" — grouped attention (models/qwen3_next.py
`project_gated`, models/cohere2_moe.py `attend_grouped`): `num_heads`
query heads on `kv_heads` keys and values of `head_dim`, query head n
reading K/V head n // (num_heads / kv_heads); q and k RMS-normed over
the head (a plain gain, as every norm here) and then rotated over the
whole head, pairs i and i + head_dim / 2; causal softmax at
head_dim^-1/2.  A token's cache row in such a layer is its `kv_heads`
keys (normed, rotated) and values; no other layer owns rows.

FFN — the first `dense_layers` layers W_d (silu(W_g h) * W_u h) at
`d_ffn`; the others route (moe/dropless.py, models/cohere2_moe.py
`routed_ffn`): s = sigmoid(h W_r) over `num_experts` in float32; the
`top_k` with the largest s + b (b the layer's `select_bias`: it chooses
and does not weigh); weights s_i / (sum s_i + `renorm_eps`) times
`route_scale`; each expert the same gated form at `d_expert`, among the
`experts_held` this chip holds from `first_expert` on.  No shared expert.

The serving engine runs the model through `layer_spec()`
(serving/layers.py); `apply` is the uncached forward the tests compare
with `benchmarks/reference/lfm2_moe.py`.  Training it, the exchange
between the chips that share a layer's experts and a mesh are not built.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .cohere2_moe import attend_grouped, expert_ffn
from .deepseek_v2 import rms_norm_plain
from .evabyte import matmul32, silu_gated_ffn
from .layer_spec import LayerSpec
from .qwen3_next import project_gated

LAYER_TYPES = ("conv", "full_attention")


@dataclasses.dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536          # rows of the vocabulary held
    max_seq_len: int = 128000
    layer_types: tuple = ("conv", "conv", "full_attention", "conv") * 10
    d_model: int = 2048
    d_ffn: int = 11776               # the leading dense layers' width
    dense_layers: int = 2
    num_heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    conv_taps: int = 3
    d_expert: int = 1536
    num_experts: int = 64            # the router's outputs
    top_k: int = 4
    route_scale: float = 1.0
    renorm_eps: float = 1e-6
    experts_held: int = 0            # 0: every expert is held here
    first_expert: int = 0
    norm_eps: float = 1e-5
    # seeded weights only: every matrix N(0, init_std) — W_q and W_k
    # `qk_scale` times that: the head's norm removes the scale, so the
    # function is the same, and a norm left out shows (at init_std alone
    # a head's q and k already have an RMS of ~1) —, the choosing bias
    # N(0, bias_std) (NOT zero: a bias let into the weights shows), the
    # taps uniform in +-conv_taps^-1/2 (a depthwise Conv1d's default)
    init_std: float = 0.02
    qk_scale: float = 4.0
    bias_std: float = 0.01
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if not self.layer_types or set(self.layer_types) - set(LAYER_TYPES):
            raise ValueError(f"layer_types {self.layer_types!r} says of "
                             f"each layer one of {LAYER_TYPES}")
        if self.num_heads % self.kv_heads or self.head_dim % 2:
            raise ValueError(
                f"num_heads {self.num_heads} must be a multiple of kv_heads "
                f"({self.kv_heads}) and head_dim {self.head_dim} even")
        if self.conv_taps < 2:
            raise ValueError("the convolution has at least 2 taps")
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
        return len(self.layer_types)

    @property
    def held(self) -> int:
        """Routed experts whose matrices are here."""
        return self.experts_held or self.num_experts

    def attends(self, layer: int) -> bool:
        return self.layer_types[layer] == "full_attention"


# ---------------------------------------------------------------------------
# the gated short convolution (serving/layers.py reaches it through
# models/layer_spec.py STATE_MIXERS)
# ---------------------------------------------------------------------------


def conv_mix(spec, p, h, rows, n_valid, live=None):
    """The gated short convolution over h [B, T, D] (normed) from a
    request's kept inputs `rows` [B, K - 1, D], the gated g of its last
    K - 1 positions (zeros before its start); `n_valid` [B]: how many of
    the T positions are real.  -> (out [B, T, D] float32, rows), the
    rows moved on by the valid positions and by nothing else: a sequence
    with none gets its own back.  `live` (a decode step's list of
    running slots) is not needed: every sequence's rows are two of D."""
    T, K = h.shape[1], spec.conv_taps
    gate_in, gate_out, u = jnp.split(matmul32(h, p["in"]), 3, axis=-1)
    # the convolution's inputs at the dtype they are kept in between
    # calls: where a call ends must not show
    seq = jnp.concatenate(
        [rows, (gate_in * u).astype(rows.dtype)], axis=1).astype(jnp.float32)
    w = p["conv_w"].astype(jnp.float32)                       # [D, K]
    c = sum(seq[:, j:j + T] * w[:, j] for j in range(K))
    # the last K - 1 VALID inputs: rows n_valid .. of [kept | call]
    rows = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
        s, n, K - 1, axis=0))(seq, n_valid).astype(rows.dtype)
    return matmul32(gate_out * c, p["out"]), rows


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class Lfm2Moe:
    """LFM2-MoE's language model as the serving engine and the tests
    take it: `init` from a key, `apply` (uncached), `layer_spec` (what
    serving builds its programs from)."""

    def __init__(self, config: Lfm2MoeConfig):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        attends = [c.attends(i) for i in range(c.num_layers)]
        return LayerSpec(
            norm="rmsnorm", positions="per_layer", attention="grouped",
            ffn="routed_experts", head="tied", eps=c.norm_eps,
            rope_theta=c.rope_theta, kv_heads=c.kv_heads,
            layer_positions=tuple("rope" if a else "none" for a in attends),
            layer_mixers=tuple("attention" if a else "conv"
                               for a in attends),
            conv_taps=c.conv_taps, conv_channels=c.d_model,
            qk_norm=True, rope_halves=True,
            top_k=c.top_k, dense_layers=c.dense_layers, scoring="sigmoid",
            renormalize=True, renorm_eps=c.renorm_eps, select_bias=True,
            route_scale=c.route_scale, experts_held=c.experts_held,
            first_expert=c.first_expert).validate()

    def init(self, rng):
        c = self.config
        d, dt, std = c.d_model, c.param_dtype, c.init_std
        H, KV, dh = c.num_heads, c.kv_heads, c.head_dim

        def normal(key, shape, scale=std):
            return (jax.random.normal(key, shape) * scale).astype(dt)

        def gated(keys, width, lead=()):
            return {"gate": normal(keys[0], lead + (d, width)),
                    "up": normal(keys[1], lead + (d, width)),
                    "down": normal(keys[2], lead + (width, d))}

        def conv(key):
            k = jax.random.split(key, 3)
            return {"in": normal(k[0], (d, 3 * d)),
                    "conv_w": jax.random.uniform(
                        k[1], (d, c.conv_taps), jnp.float32,
                        -c.conv_taps ** -0.5, c.conv_taps ** -0.5).astype(dt),
                    "out": normal(k[2], (d, d))}

        def attention(key):
            k = jax.random.split(key, 4)
            return {"q": normal(k[0], (d, H * dh), std * c.qk_scale),
                    "k": normal(k[1], (d, KV * dh), std * c.qk_scale),
                    "v": normal(k[2], (d, KV * dh)),
                    "q_norm": {"scale": jnp.ones((dh,), dt)},
                    "k_norm": {"scale": jnp.ones((dh,), dt)},
                    "o": normal(k[3], (H * dh, d))}

        def block(i, key):
            k = jax.random.split(key, 6)
            mix = {"attn": attention(k[0])} if c.attends(i) \
                else {"conv": conv(k[0])}
            mlp = gated(k[1:4], c.d_ffn) if i < c.dense_layers else {
                "router": normal(k[4], (d, c.num_experts)),
                "select_bias": jax.random.normal(
                    k[5], (c.num_experts,)) * c.bias_std,
                "experts": gated(k[1:4], c.d_expert, (c.held,))}
            return {"ln1": {"scale": jnp.ones((d,), dt)}, **mix,
                    "ln2": {"scale": jnp.ones((d,), dt)}, "mlp": mlp}

        keys = jax.random.split(rng, c.num_layers + 1)
        return {"wte": normal(keys[0], (c.vocab_size, d)),
                "blocks": [block(i, k) for i, k in enumerate(keys[1:])],
                "ln_f": {"scale": jnp.ones((d,), dt)}}

    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, vocab] float32, no
        cache: every convolution starts from rows of zeros."""
        c, spec = self.config, self.layer_spec()
        B, S = tokens.shape
        x = params["wte"][tokens].astype(jnp.float32)
        pos = jnp.arange(S)
        causal = jnp.broadcast_to(pos[None, :] <= pos[:, None], (B, S, S))
        positions = jnp.broadcast_to(pos, (B, S))
        for i, p in enumerate(params["blocks"]):
            h = rms_norm_plain(x, p["ln1"], c.norm_eps)
            if c.attends(i):
                q, k, v, _ = project_gated(c, spec, p["attn"], h, positions,
                                           True, c.param_dtype)
                mixed = matmul32(attend_grouped(q, k, v, causal),
                                 p["attn"]["o"])
            else:
                mixed, _ = conv_mix(
                    spec, p["conv"], h,
                    jnp.zeros((B, c.conv_taps - 1, c.d_model),
                              c.param_dtype),
                    jnp.full((B,), S, jnp.int32))
            x = x + mixed
            h = rms_norm_plain(x, p["ln2"], c.norm_eps)
            x = x + (silu_gated_ffn(p["mlp"], h) if i < c.dense_layers
                     else expert_ffn(spec, c, p["mlp"], h)[0])
        h = rms_norm_plain(x, params["ln_f"], c.norm_eps)
        return matmul32(h, params["wte"].T)

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
