"""Command A+ (`model_type` `cohere2_moe`): sliding-window layers with
rotary positions beside full layers with no positions at all, grouped
K/V heads, a parallel attention + FFN block, and a sigmoid-routed
mixture of experts beside averaged shared ones.

A block has ONE LayerNorm (a gain, no bias) and the residual stream in
float32: h = LN(x); x <- x + attn(h) + ffn(h).

Attention: `num_heads` query heads on `kv_heads` keys and values, query
head n reading K/V head n // (num_heads / kv_heads); no bias, no q/k
norm.  Layers follow a pattern of `period` (`local_attn_first`): the
first `period - 1` of each period are SLIDING — q and k rotated over the
whole head with GPT-J pairing (dims 2i and 2i + 1 by p theta^(-2i/dh),
`rope_interleaved`), the query at p attending keys j with
p - window < j <= p — and the last is FULL: no positions, causal over
every cached position.  A token's cache row in a layer is its `kv_heads`
keys (rotated where the layer rotates) and its `kv_heads` values.

FFN, on the same h: s = sigmoid(h W_r) over `num_experts` in float32,
the `top_k` largest, weights s_i over the sum of the chosen; every
assignment computed (moe/dropless.py), among the experts this chip
holds: `experts_held` of them from `first_expert` on (what expert
parallelism gives one chip; 0: all).  What the other chips' experts
would add is left out — it is theirs to add, before the combine — and
the shared experts, `num_shared` gated FFNs of the experts' width whose
outputs are AVERAGED, are computed once.  Their matrices lie side by
side (`gate`, `up` [D, S F], `down` [S F, D]): the mean of S gated FFNs
is one gated FFN of S times the width over S.

Tied head over the rows of the vocabulary held (`vocab_size` of them:
ids, logits and sampling are over the slice), `logit_scale` 1.

The serving engine runs the model through `layer_spec()`
(`serving/layers.py` holds the cached block, built from the pieces
here); `apply` is the uncached forward the tests compare with the plain
reference (`benchmarks/reference/cohere2_moe.py`).  Training it, a mesh
(the all-to-all between the chips that share a layer), the vision tower
and embeddings in place of token ids are not built.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..moe.dropless import (dense_expert, experts_touched,
                            held_assignments, route,
                            routed_experts)
from .evabyte import NEG_INF, matmul32
from .layer_spec import LayerSpec

# scores of a call's heads formed at once, at most: beyond it a K/V
# head's query heads at a time (a prefill chunk of 512 over 16,384 rows
# is 4.3 GB for all 128 heads, 0.54 GB for the 16 of one K/V head)
SCORE_BYTES = 1 << 29


@dataclasses.dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144         # rows of the vocabulary held
    max_seq_len: int = 200000
    num_layers: int = 32
    num_heads: int = 128
    kv_heads: int = 8
    head_dim: int = 128
    d_model: int = 4096
    d_expert: int = 4096             # a routed and a shared expert alike
    num_experts: int = 128           # the router's outputs
    top_k: int = 8
    num_shared: int = 4
    experts_held: int = 0            # 0: all of them
    first_expert: int = 0
    window: int = 4096
    period: int = 4                  # period - 1 sliding layers, one full
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    init_std: float = 0.02           # seeded weights: every matrix
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")
        if self.num_heads % self.kv_heads:
            raise ValueError(f"num_heads {self.num_heads} must be a "
                             f"multiple of kv_heads ({self.kv_heads})")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} must lie in 1.."
                             f"num_experts ({self.num_experts})")
        if self.first_expert + self.held > self.num_experts:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.held}"
                f" are not among the router's {self.num_experts}")
        if self.period < 1 or self.window < 1:
            raise ValueError("period and window must be >= 1")

    @property
    def held(self) -> int:
        """Routed experts whose matrices are here."""
        return self.experts_held or self.num_experts

    def window_of(self, layer: int) -> int:
        """0 for a full layer, else the sliding window."""
        return 0 if layer % self.period == self.period - 1 else self.window


# ---------------------------------------------------------------------------
# the pieces (shared with serving/layers.py)
# ---------------------------------------------------------------------------


def layer_norm_gain(x, p, eps):
    """LayerNorm in float32 with a gain and no bias; returns float32."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * \
        p["scale"].astype(jnp.float32)


def rope_interleaved(x, positions, theta):
    """Rotary positions over the whole head, GPT-J pairing: dims 2i and
    2i + 1 turn by p theta^(-2i/dh).  x [..., T, H, Dh], positions
    [..., T] -> float32."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[..., None] * inv     # [..., T, Dh/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def project_grouped(cfg, p, h, positions, rotate: bool, dtype):
    """h [B, T, D] at positions [B, T] -> q [B, T, H, Dh], k, v
    [B, T, KV, Dh] at `dtype`; q and k rotated in float32 first where
    the layer rotates."""
    B, T, _ = h.shape
    q = matmul32(h, p["q"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = matmul32(h, p["k"]).reshape(B, T, cfg.kv_heads, cfg.head_dim)
    v = matmul32(h, p["v"]).reshape(B, T, cfg.kv_heads, cfg.head_dim)
    if rotate:
        q = rope_interleaved(q, positions, cfg.rope_theta)
        k = rope_interleaved(k, positions, cfg.rope_theta)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _attend_heads(q, k, v, mask, scale):
    """q [B, T, n, G, Dh] on k, v [B, L, n, Dh] under mask [B, T, L] ->
    [B, T, n, G, Dh] float32: query head (n, g) reads K/V head n."""
    scores = jnp.einsum("bqngd,bknd->bngqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(
        jnp.where(mask[:, None, None, :, :], scores, NEG_INF), axis=-1)
    return jnp.einsum("bngqk,bknd->bqngd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def attend_grouped(q, k, v, mask, scale=None):
    """Softmax attention of q [B, T, H, Dh] over k, v [B, L, KV, Dh]
    under mask [B, T, L], query head n on K/V head n // (H / KV), the
    scores times `scale` (None: Dh ** -0.5) -> [B, T, H * Dh] float32.
    All heads' scores at once where they are small, a K/V head's at a
    time where they are not (`SCORE_BYTES`)."""
    B, T, H, Dh = q.shape
    L, KV = k.shape[1], k.shape[2]
    q = q.reshape(B, T, KV, H // KV, Dh)
    scale = Dh ** -0.5 if scale is None else scale
    if 4 * B * H * T * L <= SCORE_BYTES:
        return _attend_heads(q, k, v, mask, scale).reshape(B, T, H * Dh)
    out = jax.lax.map(
        lambda a: _attend_heads(a[0][:, :, None], a[1][:, :, None],
                                a[2][:, :, None], mask, scale)[:, :, 0],
        (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
         jnp.moveaxis(v, 2, 0)))                    # [KV, B, T, G, Dh]
    return jnp.moveaxis(out, 0, 2).reshape(B, T, H * Dh)


def routed_ffn(spec, cfg, p, flat, live=None):
    """flat [T, D] float32 -> (y [T, D] float32, experts [T, top_k],
    count, held): the routed FFN the layer spec describes — the router's
    `spec.scoring`, the chosen weights over their sum where
    `spec.renormalize` (chosen by the scores plus the layer's
    `select_bias` where `spec.select_bias`, the sum plus
    `spec.renorm_eps`, times `spec.route_scale`),
    the held experts' weighted sum (`spec.held`: a share of
    `cfg.num_experts`, or all; an expert's form is what its tree holds,
    kernels/expert_form.py `expert_hidden`) plus, where the tree holds
    `shared` experts, their sum or, with
    `spec.shared` "average", their mean — and what `experts_touched`
    counts the touched experts from: the experts chosen, numbered among
    the `count` held, and which assignments are `held` (None: all).
    `live` [T]: the tokens whose sum anyone reads (None: all)."""
    with jax.named_scope("moe_route"):
        weights, idx = route(flat, p["router"], spec.top_k,
                             scoring=spec.scoring,
                             renormalize=spec.renormalize,
                             select_bias=p["select_bias"]
                             if spec.select_bias else None,
                             scale=spec.route_scale,
                             renorm_eps=spec.renorm_eps)
        held, count = None, cfg.num_experts
        if spec.held is not None:
            weights, idx, held = held_assignments(weights, idx, *spec.held)
            count = spec.held[1]
    with jax.named_scope("moe_experts"):
        y = routed_experts(flat, p["experts"], weights, idx,
                           total=cfg.num_experts, held=held, live=live)
    if "shared" not in p:          # a model without shared experts
        return y, idx, count, held
    with jax.named_scope("moe_shared"):
        shared = dense_expert(p["shared"], flat)   # of the experts' form
        if spec.shared == "gated":
            shared = shared * jax.nn.sigmoid(matmul32(flat, p["shared_gate"]))
        y = y + (shared / cfg.num_shared if spec.shared == "average"
                 else shared)
    return y, idx, count, held


def expert_ffn(spec, cfg, p, h, live=None):
    """h [..., D] float32 -> (`routed_ffn` of its tokens, float32; None,
    or with `live` [tokens] how many of the experts held the live tokens
    touched, int32)."""
    y, idx, count, held = routed_ffn(spec, cfg, p,
                                     h.reshape(-1, h.shape[-1]), live)
    touched = None if live is None else \
        experts_touched(idx, live, count, held)
    return y.reshape(h.shape), touched


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class Cohere2Moe:
    """Command A+'s language model as the serving engine and the tests
    take it: `init` from a key, `apply` (uncached), `layer_spec` (what
    serving builds its programs from)."""

    def __init__(self, config: Cohere2MoeConfig):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        windows = tuple(c.window_of(i) for i in range(c.period))
        return LayerSpec(
            norm="layernorm_gain", positions="per_layer",
            attention="grouped", ffn="routed_experts", head="tied",
            eps=c.layer_norm_eps, rope_theta=c.rope_theta, top_k=c.top_k,
            kv_heads=c.kv_heads, layer_windows=windows,
            layer_positions=tuple("rope" if w else "none"
                                  for w in windows),
            residual="parallel", scoring="sigmoid", renormalize=True,
            shared="average",
            experts_held=c.experts_held, first_expert=c.first_expert,
        ).validate()

    def init(self, rng):
        c = self.config
        d, dt, std = c.d_model, c.param_dtype, c.init_std
        H, KV, dh, f = c.num_heads, c.kv_heads, c.head_dim, c.d_expert

        def normal(key, shape, scale=std):
            return (jax.random.normal(key, shape) * scale).astype(dt)

        def gated(keys, width, lead=()):
            return {"gate": normal(keys[0], lead + (d, width)),
                    "up": normal(keys[1], lead + (d, width)),
                    "down": normal(keys[2], lead + (width, d))}

        def block(key):
            k = jax.random.split(key, 11)
            return {
                "ln1": {"scale": jnp.ones((d,), dt)},
                "attn": {"q": normal(k[0], (d, H * dh)),
                         "k": normal(k[1], (d, KV * dh)),
                         "v": normal(k[2], (d, KV * dh)),
                         "o": normal(k[3], (H * dh, d))},
                "mlp": {"router": normal(k[4], (d, c.num_experts)),
                        "experts": gated(k[5:8], f, (c.held,)),
                        "shared": gated(k[8:11], c.num_shared * f)},
            }

        keys = jax.random.split(rng, c.num_layers + 1)
        return {"wte": normal(keys[0], (c.vocab_size, d)),
                "blocks": [block(k) for k in keys[1:]],
                "ln_f": {"scale": jnp.ones((d,), dt)}}

    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, vocab] float32, no
        cache."""
        c, spec = self.config, self.layer_spec()
        B, S = tokens.shape
        x = params["wte"][tokens].astype(jnp.float32)
        pos = jnp.arange(S)
        positions = jnp.broadcast_to(pos, (B, S))
        causal = pos[None, :] <= pos[:, None]
        for i, p in enumerate(params["blocks"]):
            w = c.window_of(i)
            h = layer_norm_gain(x, p["ln1"], c.layer_norm_eps)
            q, k, v = project_grouped(c, p["attn"], h, positions, bool(w),
                                      c.param_dtype)
            mask = causal & (pos[None, :] > pos[:, None] - w) if w \
                else causal
            a = attend_grouped(q, k, v, jnp.broadcast_to(mask, (B, S, S)))
            x = x + matmul32(a, p["attn"]["o"]) + \
                expert_ffn(spec, c, p["mlp"], h)[0]
        h = layer_norm_gain(x, params["ln_f"], c.layer_norm_eps)
        return matmul32(h, params["wte"].T)

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
