"""EvaByte — a byte-level decoder whose attention is EVA (Zheng et al.,
"Efficient Attention via Control Variates", ICLR 2023) in the
deterministic form the released model ships.

One position is one byte: the vocabulary is 320 (bytes plus specials) and
there is no tokenizer.  A block is pre-norm with the residual stream in
float32 (`fp32_skip_add`): RMSNorm whose scale is 1 + g
(`norm_add_unit_offset`), rotary positions over the whole head on q and
k, a SiLU-gated FFN.  The output head gives `num_pred_heads` x 320
logits in float32 (`fp32_logits`); head 0 is the next byte, the others
are the multi-byte prediction heads (computed, not used for decoding
here).

Attention, per head, with window W and chunk C: query i attends in ONE
softmax to the exact keys of its own window up to itself and to one
summary (k~_c, v~_c) of every chunk c that lies in an earlier window.  A
chunk's summary pools its own C keys and values with softmax weights
from two learned vectors per head: k~_c = sum_j softmax_j(s k_j.mu) k_j,
v~_c = sum_j softmax_j(s k_j.phi) v_j, s = head_dim^-1/2, keys taken
after RoPE.  With W >= S, or with C = 1, this is plain causal softmax
attention.

The serving engine runs the model through `layer_spec()`
(`serving/layers.py` holds the cached block); `apply` here is the
uncached full-sequence forward the tests compare with the plain
reference (`benchmarks/reference/evabyte.py`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .layer_spec import LayerSpec

NEG_INF = -1e30


@dataclasses.dataclass
class EvaByteConfig:
    vocab_size: int = 320
    max_seq_len: int = 32768
    num_layers: int = 32
    num_heads: int = 32
    d_model: int = 4096
    d_ff: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    init_std: float = 0.01275
    # seeded weights only: the scale of o_proj (None: init_std) and of
    # the pooling vectors
    attn_out_std: Optional[float] = None
    pool_std: float = 1.0
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.d_model % self.num_heads:
            raise ValueError("d_model must divide into num_heads")
        if self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size {self.window_size} must be a multiple of "
                f"chunk_size {self.chunk_size}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


# ---------------------------------------------------------------------------
# the pieces (shared with serving/layers.py)
# ---------------------------------------------------------------------------


def rms_norm(x, p, eps):
    """RMSNorm in float32 with the scale 1 + g; returns float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y * (1.0 + p["scale"].astype(jnp.float32))


def rope(x, positions, theta):
    """Rotary positions over the whole head, half-split pairing.
    x [..., T, H, Dh], positions [..., T] -> float32."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[..., None] * inv     # [..., T, Dh/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :dh // 2], x32[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def matmul32(x, w):
    """x @ w at the weights' dtype with float32 accumulation and
    result."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def silu_gated_ffn(p, h):
    """(silu(h Wg) * h Wu) Wd -> float32."""
    g = matmul32(h, p["gate"])
    u = matmul32(h, p["up"])
    return matmul32(jax.nn.silu(g) * u, p["down"])


def chunk_summaries(k, v, mu, phi):
    """k, v [..., C, H, Dh] -> (k~, v~) [..., H, Dh] float32: the
    chunk's keys and values pooled with softmax weights from the
    per-head vectors `mu`, `phi` [H, Dh]."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    s = k.shape[-1] ** -0.5
    wk = jax.nn.softmax(s * jnp.einsum(
        "...chd,hd->...ch", k32, mu.astype(jnp.float32)), axis=-2)
    wv = jax.nn.softmax(s * jnp.einsum(
        "...chd,hd->...ch", k32, phi.astype(jnp.float32)), axis=-2)
    return (jnp.einsum("...ch,...chd->...hd", wk, k32),
            jnp.einsum("...ch,...chd->...hd", wv, v32))


def eva_attention_full(q, k, v, mu, phi, *, window: int, chunk: int):
    """Uncached EVA over a whole sequence.  q, k, v [B, S, H, Dh] (after
    RoPE) -> [B, S, H, Dh] float32."""
    B, S, H, Dh = q.shape
    scale = Dh ** -0.5
    n_chunks = S // chunk                     # a ragged tail never closes
    pos = jnp.arange(S)
    local = (pos[None, :] <= pos[:, None]) & \
        (pos[None, :] // window == pos[:, None] // window)      # [S, S]
    s_loc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
    s_loc = jnp.where(local[None, None], s_loc, NEG_INF)
    if n_chunks:
        head = lambda t: t[:, :n_chunks * chunk].reshape(
            B, n_chunks, chunk, H, Dh)
        ks, vs = chunk_summaries(head(k), head(v), mu, phi)
        ks, vs = ks.astype(k.dtype), vs.astype(v.dtype)
        c = jnp.arange(n_chunks)
        remote = (c[None, :] + 1) * chunk <= \
            (pos[:, None] // window) * window                    # [S, Nc]
        s_rem = jnp.einsum("bqhd,bchd->bhqc", q, ks,
                           preferred_element_type=jnp.float32) * scale
        s_rem = jnp.where(remote[None, None], s_rem, NEG_INF)
        scores = jnp.concatenate([s_loc, s_rem], axis=-1)
        values = jnp.concatenate([v, vs], axis=1)
    else:
        scores, values = s_loc, v
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(values.dtype), values,
                      preferred_element_type=jnp.float32)


def project_qkv(p, h, positions, theta, num_heads, dtype):
    """h [..., T, D] -> q, k, v [..., T, H, Dh] at `dtype`, q and k
    rotated in float32 first."""
    shape = h.shape[:-1] + (num_heads, -1)
    q = rope(matmul32(h, p["q"]).reshape(shape), positions, theta)
    k = rope(matmul32(h, p["k"]).reshape(shape), positions, theta)
    v = matmul32(h, p["v"]).reshape(shape)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def output_logits(params, x, eps):
    """Final norm and the `num_pred_heads` x vocab head, in float32 at
    full precision (`fp32_logits`): it is 0.3 % of the model's weights."""
    h = rms_norm(x, params["ln_f"], eps)
    return jnp.dot(h, params["lm_head"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class EvaByte:
    """EvaByte as the serving engine and the tests take it: `init` from
    a key, `apply` (uncached), `layer_spec` (what serving builds its
    programs from)."""

    def __init__(self, config: EvaByteConfig):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        return LayerSpec(norm="rmsnorm_unit_offset", positions="rope",
                         attention="eva", ffn="silu_gated", head="untied",
                         eps=c.rms_norm_eps, rope_theta=c.rope_theta,
                         window=c.window_size, chunk=c.chunk_size,
                         sample_vocab=c.vocab_size,
                         fp32_logits=True).validate()

    def init(self, rng):
        c = self.config
        d, f, dt = c.d_model, c.d_ff, c.param_dtype
        std = c.init_std
        o_std = std if c.attn_out_std is None else c.attn_out_std

        def normal(key, shape, scale):
            return (jax.random.normal(key, shape) * scale).astype(dt)

        def block(key):
            k = jax.random.split(key, 9)
            return {
                "ln1": {"scale": jnp.zeros((d,), dt)},
                "attn": {"q": normal(k[0], (d, d), std),
                         "k": normal(k[1], (d, d), std),
                         "v": normal(k[2], (d, d), std),
                         "o": normal(k[3], (d, d), o_std),
                         "mu": normal(k[4], (c.num_heads, c.head_dim),
                                      c.pool_std),
                         "phi": normal(k[5], (c.num_heads, c.head_dim),
                                       c.pool_std)},
                "ln2": {"scale": jnp.zeros((d,), dt)},
                "mlp": {"gate": normal(k[6], (d, f), std),
                        "up": normal(k[7], (d, f), std),
                        "down": normal(k[8], (f, d), std)},
            }

        keys = jax.random.split(rng, c.num_layers + 2)
        return {
            "wte": normal(keys[0], (c.vocab_size, d), std),
            "blocks": [block(k) for k in keys[2:]],
            "ln_f": {"scale": jnp.zeros((d,), dt)},
            "lm_head": normal(keys[1],
                              (d, c.num_pred_heads * c.vocab_size), std),
        }

    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, num_pred_heads * vocab]
        float32, no cache."""
        c = self.config
        B, S = tokens.shape
        dt = c.param_dtype
        x = params["wte"][tokens].astype(jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        for p in params["blocks"]:
            h = rms_norm(x, p["ln1"], c.rms_norm_eps)
            q, k, v = project_qkv(p["attn"], h, positions, c.rope_theta,
                                  c.num_heads, dt)
            a = eva_attention_full(q, k, v, p["attn"]["mu"],
                                   p["attn"]["phi"], window=c.window_size,
                                   chunk=c.chunk_size)
            x = x + matmul32(a.reshape(B, S, -1), p["attn"]["o"])
            x = x + silu_gated_ffn(
                p["mlp"], rms_norm(x, p["ln2"], c.rms_norm_eps))
        return output_logits(params, x, c.rms_norm_eps)

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
