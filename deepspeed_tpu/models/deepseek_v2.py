"""DeepSeek-V2 (and -Lite): multi-head latent attention (MLA) and a
mixture of routed experts beside shared ones (`model_type` `deepseek_v2`).

A block is pre-norm with plain RMSNorm (gain w, no unit offset) and the
residual stream in float32.

Attention keeps ONE latent row a token, shared by all heads: h W_kv_a ->
[c (`kv_lora_rank`) | k_r (`qk_rope_head_dim`)], c RMS-normed, k_r
rotated.  That row — after the norm and the rotation — is what a cache
holds.  A head's query is [q_nope | q_rope], q_rope rotated.  Two ways
from rows to attention, equal in exact arithmetic:

* expanded (`attend_expanded`): [k_nope | v] = c W_kv_b for every row,
  score = (q_nope.k_nope + q_rope.k_r) s, o = sum p v.  Cheap where many
  queries share the expansion: the uncached forward, a prefill chunk.
* absorbed (`attend_absorbed`): W_kv_b's two halves move to the query
  side, q' = q_nope W_UK^T (per head, nope -> rank), score = (q'.c +
  q_rope.k_r) s, o_lat = sum p c, o = o_lat W_UV: attention over the rows
  as they lie, all heads on one row as in multi-query attention.  Cheap
  where a query stands alone: decode.  `absorb` decides from the counts
  of queries and rows.  It is `absorbed_attention` (the two W_kv_b
  products) around `attend_rows` (scores, softmax and weighted sum over
  rows handed in); a cached decode step puts a kernel that reads the
  rows where they lie in `attend_rows`' place (serving/layers.py).

s = (nope + rope)^-1/2, times m(mscale_all_dim)^2 under YaRN.  Rotary
positions cover the `rope` dims only, half-split pairing, with YaRN's
blended frequencies (`yarn_inv_freq`) and cos, sin times
m(mscale) / m(mscale_all_dim), m(s) = 0.1 s ln(factor) + 1.

The pieces also serve the families built from them
(models/glm_moe_dsa.py): a config with `q_lora_rank` > 0 takes its
queries through a low-rank path, c_q = RMSNorm(h W_q_a), q = c_q W_q_b
(`latent_project(..., with_cq=True)` hands c_q on: an indexer reads
it), and one with `rope_interleave` pairs dims 2i and 2i + 1 where this
model pairs i and i + dr/2.  A learned selection of the rows a query
attends reaches `attend_expanded` and `attend_absorbed` as what they
already take: the rows (all of a sequence's, or the chosen ones
gathered) and a mask [B, T, K] that is False for a row the query did
not choose.

FFN: the first `first_k_dense` layers a SiLU-gated FFN; the others a
float32 softmax router over `num_experts`, the `top_k` largest kept
unrenormalised, every assignment computed (moe/dropless.py), plus the
shared experts as one gated FFN counted once.

The serving engine runs the model through `layer_spec()`
(`serving/layers.py` holds the cached block, built from the pieces
here); `apply` is the uncached forward the tests compare with the plain
reference (`benchmarks/reference/deepseek_v2.py`).  Training it, a mesh,
and an expert layer that holds a share of the experts are not built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..moe.dropless import route, routed_experts
from .evabyte import NEG_INF, matmul32, silu_gated_ffn
from .layer_spec import LayerSpec


class Yarn(NamedTuple):
    """`rope_scaling` of type "yarn", as `config.json` gives it."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass
class DeepSeekV2Config:
    vocab_size: int = 102400
    max_seq_len: int = 4096
    num_layers: int = 27
    num_heads: int = 16
    d_model: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 10944                # the leading dense layers' width
    first_k_dense: int = 1
    num_experts: int = 64
    top_k: int = 6
    num_shared_experts: int = 2
    d_expert: int = 1408
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    yarn: Optional[Yarn] = Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    q_lora_rank: int = 0             # > 0: queries through c_q of this rank
    rope_interleave: bool = False    # rotary pairs (2i, 2i + 1)
    # seeded weights only: every matrix N(0, init_std); the router's own
    # scale decides how sharply it picks (at init_std a softmax over the
    # experts is flat)
    init_std: float = 0.02
    router_std: float = 0.02
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary positions need an even "
                             "qk_rope_head_dim")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} must lie in 1.."
                             f"num_experts ({self.num_experts})")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError("first_k_dense must lie in 0..num_layers")
        if self.yarn is not None and not isinstance(self.yarn, Yarn):
            self.yarn = Yarn(**self.yarn)

    @property
    def head_dim(self) -> int:
        """A query's width: [q_nope | q_rope]."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A cache row: [c | k_r], one a token for all heads."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# ---------------------------------------------------------------------------
# the pieces (shared with serving/layers.py)
# ---------------------------------------------------------------------------


def rms_norm_plain(x, p, eps):
    """RMSNorm in float32 with the gain w; returns float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y * p["scale"].astype(jnp.float32)


def yarn_mscale(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(head_dim: int, yarn: Optional[Yarn]) -> float:
    """head_dim^-1/2, times m(mscale_all_dim)^2 under YaRN."""
    m = yarn_mscale(yarn.factor, yarn.mscale_all_dim) if yarn else 1.0
    return head_dim ** -0.5 * m * m


def yarn_inv_freq(dim: int, theta: float, yarn: Optional[Yarn]):
    """The dim/2 inverse frequencies [float32]: theta^(-2i/dim), blended
    under YaRN with that / factor by a linear ramp between the dims
    whose wavelengths make `beta_fast` and `beta_slow` turns over the
    original context."""
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return plain

    def turns_dim(turns):
        return dim * math.log(yarn.original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_dim(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / yarn.factor * ramp


def rope_part(x, positions, theta, yarn, interleave: bool = False):
    """Rotary positions over all of x's last axis (the rotary part of a
    head), half-split pairing — or, with `interleave`, dims 2i and
    2i + 1 together.  x [..., T, (H,) dr] with positions [..., T] ->
    float32; `x.ndim - positions.ndim` trailing axes ride."""
    dr = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * \
        yarn_inv_freq(dr, theta, yarn)
    m = (yarn_mscale(yarn.factor, yarn.mscale)
         / yarn_mscale(yarn.factor, yarn.mscale_all_dim)) if yarn else 1.0
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    for _ in range(x.ndim - positions.ndim - 1):
        cos, sin = cos[..., None, :], sin[..., None, :]
    x32 = x.astype(jnp.float32)
    if interleave:
        a, b = x32[..., 0::2], x32[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x32[..., :dr // 2], x32[..., dr // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latent_project(cfg, p, h, positions, dtype, with_cq: bool = False):
    """h [B, T, D] at positions [B, T] -> (q_nope [B, T, H, nope],
    q_rope [B, T, H, rope] rotated, rows [B, T, rank + rope]: the
    latent c after its norm beside the rotated key), at `dtype`; with
    `with_cq` a fourth: the queries' own latent c_q [B, T, q_lora_rank]
    after its norm, float32."""
    B, T, _ = h.shape
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    pair = cfg.rope_interleave
    c_q = None
    if cfg.q_lora_rank:
        c_q = rms_norm_plain(matmul32(h, p["q_a"]), p["q_norm"],
                             cfg.rms_norm_eps)
        q = matmul32(c_q, p["q_b"])
    else:
        q = matmul32(h, p["q"])
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    q_rope = rope_part(q[..., nope:], positions, cfg.rope_theta, cfg.yarn,
                       pair)
    ckr = matmul32(h, p["kv_a"])
    c = rms_norm_plain(ckr[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
    k_r = rope_part(ckr[..., rank:], positions, cfg.rope_theta, cfg.yarn,
                    pair)
    rows = jnp.concatenate([c, k_r], axis=-1)
    out = (q[..., :nope].astype(dtype), q_rope.astype(dtype),
           rows.astype(dtype))
    return out + (c_q,) if with_cq else out


def _softmax_over_rows(scores, mask):
    """scores [B, H, T, K] float32, mask [B, T, K] -> probabilities."""
    return jax.nn.softmax(
        jnp.where(mask[:, None, :, :], scores, NEG_INF), axis=-1)


def attend_expanded(cfg, kv_b, q_nope, q_rope, rows, mask):
    """Rows expanded through W_kv_b to per-head keys and values.
    q_* [B, T, H, .], rows [B, K, rank + rope], mask [B, T, K] ->
    [B, T, H * v] float32."""
    B, K, _ = rows.shape
    H, nope, rank = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    kv = matmul32(rows[..., :rank], kv_b).astype(rows.dtype).reshape(
        B, K, H, nope + cfg.v_head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :nope],
                        preferred_element_type=jnp.float32) + \
        jnp.einsum("bqhd,bkd->bhqk", q_rope, rows[..., rank:],
                   preferred_element_type=jnp.float32)
    probs = _softmax_over_rows(
        scores * softmax_scale(cfg.head_dim, cfg.yarn), mask)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(rows.dtype),
                     kv[..., nope:], preferred_element_type=jnp.float32)
    return out.reshape(B, -1, H * cfg.v_head_dim)


def attend_rows(q_row, rows, mask, rank: int, scale: float):
    """Attention over latent rows as they lie: q_row [B, T, H, width]
    (a head's absorbed query beside its rotated part), rows [B, K,
    width], mask [B, T, K] -> [B, H, T, rank] float32, the weighted sum
    of the rows' first `rank` values (the latent c)."""
    scores = jnp.einsum("bqhw,bkw->bhqk", q_row, rows,
                        preferred_element_type=jnp.float32)
    probs = _softmax_over_rows(scores * scale, mask)
    return jnp.einsum("bhqk,bkr->bhqr", probs.astype(rows.dtype),
                      rows[..., :rank], preferred_element_type=jnp.float32)


def absorbed_attention(cfg, kv_b, q_nope, q_rope, dtype, attend):
    """W_UK absorbed into the query and W_UV into the output, around
    `attend`: the queries as rows-shaped [B, T, H, rank + rope] at
    `dtype` -> [B, H, T, rank] float32 (`attend_rows`, or a kernel that
    reads the rows where they lie).  -> [B, T, H * v] float32."""
    B = q_nope.shape[0]
    H, nope, rank = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    w = kv_b.reshape(rank, H, nope + cfg.v_head_dim)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(w.dtype),
                       w[..., :nope], preferred_element_type=jnp.float32)
    o_lat = attend(jnp.concatenate([q_lat.astype(dtype), q_rope], axis=-1))
    out = jnp.einsum("bhqr,rhd->bqhd", o_lat.astype(w.dtype), w[..., nope:],
                     preferred_element_type=jnp.float32)
    return out.reshape(B, -1, H * cfg.v_head_dim)


def attend_absorbed(cfg, kv_b, q_nope, q_rope, rows, mask):
    """W_UK absorbed into the query and W_UV into the output: attention
    over the rows as they lie.  Same arguments and result as
    `attend_expanded`."""
    return absorbed_attention(
        cfg, kv_b, q_nope, q_rope, rows.dtype,
        lambda q_row: attend_rows(q_row, rows, mask, cfg.kv_lora_rank,
                                  softmax_scale(cfg.head_dim, cfg.yarn)))


def absorb(cfg, n_queries: int, n_rows: int) -> bool:
    """Whether the absorbed path multiplies less than the expanded one
    for `n_queries` queries a sequence over `n_rows` rows: expanding
    costs rank x H (nope + v) a row whatever the queries, absorbing
    makes every (query, head, row) product rank + rope + rank wide
    instead of nope + rope + v, and moves each query through W_UK and
    W_UV."""
    H, nope, v = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    expanded = n_rows * rank * H * (nope + v) + \
        n_queries * H * n_rows * (nope + rope + v)
    absorbed = n_queries * H * (n_rows * (2 * rank + rope)
                                + rank * (nope + v))
    return absorbed < expanded


def expert_ffn(cfg, p, h, live=None):
    """h [..., D] float32 -> the routed experts' weighted sum plus the
    shared experts, float32, and the experts chosen [tokens, top_k].
    `live` [tokens]: the tokens whose sum anyone reads (None: all)."""
    flat = h.reshape(-1, h.shape[-1])
    with jax.named_scope("moe_route"):
        weights, idx = route(flat, p["router"], cfg.top_k)
    with jax.named_scope("moe_experts"):
        y = routed_experts(flat, p["experts"], weights, idx, live=live)
    with jax.named_scope("moe_shared"):
        y = y + silu_gated_ffn(p["shared"], flat)
    return y.reshape(h.shape), idx


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class DeepSeekV2:
    """DeepSeek-V2 as the serving engine and the tests take it: `init`
    from a key, `apply` (uncached), `layer_spec` (what serving builds
    its programs from)."""

    def __init__(self, config: DeepSeekV2Config):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        return LayerSpec(norm="rmsnorm", positions="rope",
                         attention="latent", ffn="routed_experts",
                         head="untied", eps=c.rms_norm_eps,
                         rope_theta=c.rope_theta,
                         top_k=c.top_k, dense_layers=c.first_k_dense,
                         latent_width=c.latent_width).validate()

    def init(self, rng):
        c = self.config
        d, dt, std = c.d_model, c.param_dtype, c.init_std
        H, E, f = c.num_heads, c.num_experts, c.d_expert

        def normal(key, shape, scale=std):
            return (jax.random.normal(key, shape) * scale).astype(dt)

        def gated(keys, width, lead=()):
            return {"gate": normal(keys[0], lead + (d, width)),
                    "up": normal(keys[1], lead + (d, width)),
                    "down": normal(keys[2], lead + (width, d))}

        def block(key, dense):
            k = jax.random.split(key, 11)
            if dense:
                mlp = gated(k[4:7], c.d_ff)
            else:
                mlp = {"router": normal(k[4], (d, E), c.router_std),
                       "experts": gated(k[5:8], f, (E,)),
                       "shared": gated(k[8:11], c.num_shared_experts * f)}
            return {
                "ln1": {"scale": jnp.ones((d,), dt)},
                "attn": {"q": normal(k[0], (d, H * c.head_dim)),
                         "kv_a": normal(k[1], (d, c.latent_width)),
                         "kv_norm": {"scale": jnp.ones((c.kv_lora_rank,),
                                                       dt)},
                         "kv_b": normal(k[2], (c.kv_lora_rank, H * (
                             c.qk_nope_head_dim + c.v_head_dim))),
                         "o": normal(k[3], (H * c.v_head_dim, d))},
                "ln2": {"scale": jnp.ones((d,), dt)},
                "mlp": mlp,
            }

        keys = jax.random.split(rng, c.num_layers + 2)
        return {
            "wte": normal(keys[0], (c.vocab_size, d)),
            "blocks": [block(k, i < c.first_k_dense)
                       for i, k in enumerate(keys[2:])],
            "ln_f": {"scale": jnp.ones((d,), dt)},
            "lm_head": normal(keys[1], (d, c.vocab_size)),
        }

    def apply(self, params, tokens, absorbed: bool = False):
        """tokens [B, S] int32 -> logits [B, S, vocab] float32, no
        cache; `absorbed` takes the decode path's products."""
        c = self.config
        B, S = tokens.shape
        x = params["wte"][tokens].astype(jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        mask = jnp.broadcast_to(
            jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], (B, S, S))
        attend = attend_absorbed if absorbed else attend_expanded
        for i, p in enumerate(params["blocks"]):
            h = rms_norm_plain(x, p["ln1"], c.rms_norm_eps)
            q_nope, q_rope, rows = latent_project(
                c, p["attn"], h, positions, c.param_dtype)
            a = attend(c, p["attn"]["kv_b"], q_nope, q_rope, rows, mask)
            x = x + matmul32(a, p["attn"]["o"])
            h = rms_norm_plain(x, p["ln2"], c.rms_norm_eps)
            if i < c.first_k_dense:
                x = x + silu_gated_ffn(p["mlp"], h)
            else:
                x = x + expert_ffn(c, p["mlp"], h)[0]
        h = rms_norm_plain(x, params["ln_f"], c.rms_norm_eps)
        return matmul32(h, params["lm_head"])

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
