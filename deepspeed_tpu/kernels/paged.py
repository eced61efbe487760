"""Pallas decode-path paged attention (op 1): fused block-table gather
+ online-softmax attention over the PagedKVCache, with the PR-15
quantized-KV dequant fused into the gather.

The jnp oracle (`paged_attention_reference`) is the EXACT expression
serving/layers.py's `_paged_attend` always ran — gather the table's
rows, dequantize if the cache is quantized, one fp32 einsum/softmax/
einsum chain under the `q_pos >= k_idx` mask.  Wherever the registry
picks the oracle (all of tier-1 on CPU) serving output stays
bit-identical to the pre-registry code, which is what keeps the
serving-vs-generate pins green.

The kernel removes the materialised `[B, L, H, Dh]` gather: each
(slot·head) program walks the slot's block table a cache block at a
time — the table rides scalar prefetch, so the BlockSpec index map
turns each step into a direct async copy of ONE `[block_size, Dh]`
cache tile into VMEM (the fused gather), streamed through the same
online-softmax accumulator as ops/transformer/flash_attention.py.  For
quantized caches the tile arrives as (codes, scales) and dequantizes
in-register — int4 nibble decode included — so the HBM read is the
COMPRESSED cache, the whole point of quantized KV.

Parity: tolerance-bounded (online-softmax tiling vs one fused softmax),
the attention-op contract.  Trash/garbage blocks beyond a slot's length
are killed by the mask in both impls: the oracle's softmax underflows
their NEG_INF scores to exactly 0, the kernel zeroes fully-masked
tiles explicitly (`p = where(s <= NEG_INF/2, 0, p)` — the
flash_attention bias-path guard, since a tile past the horizon has no
live key to anchor the running max).

TPU-native layout: caches are viewed as `[rows, H * width]` (a free
reshape) so each gathered tile is a `(block_size, width)` block —
one the chip's compiler tiles only when `width % 128 == 0`, so the
registry refuses head_dim 64 (every GPT-2 size) and int4 below
head_dim 256 by name, and gates on small T (the q rows unroll over
scalar-prefetched positions).  Scales ride the block's whole
`(block_size, H)` tile and the head's column is selected in-kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import pallas_backend
from ..models.generation import NEG_INF


def _clamp(i):
    return jnp.maximum(i, 0)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY))


def kv_read(c, rows, kv_mode: str = "dense"):
    """Gather cache rows `rows` [B, L] -> [B, L, H, Dh].  Dense reads
    come back at the cache dtype; quantized caches ((payload, scales)
    pairs) dequantize the gathered rows to fp32.  THE gather the oracle
    and serving/programs.py share."""
    if kv_mode == "dense":
        return c[rows]
    from ..runtime.comm.quant import dequantize_rows

    payload, scales = c
    return dequantize_rows(payload[rows], scales[rows], kv_mode)


def paged_attention_reference(q, ck, cv, rows, q_pos, *,
                              kv_mode: str = "dense",
                              block_size: int = 0):
    """The `_paged_attend` attention core (serving/layers.py), verbatim: q [B, T, H, Dh],
    caches addressed by flat rows [B, L], q_pos [B, T] absolute
    positions -> attn [B, T, H, Dh] (at the cache/dequant dtype)."""
    del block_size  # kernel tiling knob; the gather needs only rows
    Dh = q.shape[-1]
    keys = kv_read(ck, rows, kv_mode)      # [B, L, H, Dh]
    vals = kv_read(cv, rows, kv_mode)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) * (Dh ** -0.5)
    L = rows.shape[1]
    k_idx = jnp.arange(L)[None, None, :]
    mask = q_pos[:, :, None] >= k_idx            # [B, T, L]
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vals.dtype), vals)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _decode_nibbles(raw, width, full):
    """uint8 [rows, width] -> int8 codes [rows, full] (quant.py's
    low-nibble-first two's-complement decode)."""
    lo = (raw & jnp.uint8(0x0F)).astype(jnp.int8)
    hi = ((raw >> 4) & jnp.uint8(0x0F)).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(raw.shape[0], full)


def _f16_bits_to_f32(bits):
    """uint16 fp16 bit patterns (non-negative: they are scales) -> the
    fp32 values, in integer ops: the chip loads no fp16 vectors
    ("Invalid vector type for load")."""
    b = bits.astype(jnp.uint32)
    exp, man = (b >> 10) & jnp.uint32(0x1F), b & jnp.uint32(0x3FF)
    # normals re-bias the exponent (15 -> 127); exponent 31 is inf/nan
    exp32 = jnp.where(exp == 31, jnp.uint32(255), exp + jnp.uint32(112))
    normal = jax.lax.bitcast_convert_type((exp32 << 23) | (man << 13),
                                          jnp.float32)
    sub = man.astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return jnp.where(exp == 0, sub, normal)


def _tile_kv(ref, s_ref, head, kv_mode, Dh, marker):
    """One gathered cache tile -> fp32 [block_size, Dh], dequantized
    in-register for quantized caches (the fused dequant).  The scales
    arrive as the block's full `(block_size, H)` tile — a one-column
    block is not one the chip's compiler tiles — and this head's column
    is picked by a masked lane reduction."""
    raw = ref[...]
    if kv_mode == "dense":
        return raw.astype(jnp.float32)
    if kv_mode == "int4":
        codes = _decode_nibbles(raw, raw.shape[-1], Dh)
    else:
        codes = raw.astype(jnp.int8)
    scales = _f16_bits_to_f32(s_ref[...])                    # (bs, H)
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    scale = jnp.sum(jnp.where(lane == head, scales, 0.0), axis=1,
                    keepdims=True)                           # (bs, 1)
    # compared as fp32: the chip has no int8 vector comparison
    codes = codes.astype(jnp.float32)
    return jnp.where(codes == marker, jnp.float32(jnp.nan), codes * scale)


def _paged_kernel(tbl, qp, q_ref, *rest, scale, bs, W, H, T, Dh,
                  kv_mode, marker):
    if kv_mode == "dense":
        k_ref, v_ref, o_ref, acc, m_s, l_s = rest
        ks_ref = vs_ref = None
    else:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc, m_s, l_s = rest
    bh = pl.program_id(0)
    a = pl.program_id(1)
    r = jax.lax.div(bh, H)
    head = jax.lax.rem(bh, H)

    @pl.when(a == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32) * scale          # (T, Dh)
    k = _tile_kv(k_ref, ks_ref, head, kv_mode, Dh, marker)  # (bs, Dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    kidx = a * bs + jax.lax.broadcasted_iota(jnp.int32, (T, bs), 1)
    # T is tiny (1 decode, draft+1 verify): unroll the scalar position
    # reads instead of carrying a [T]-shaped operand through VMEM
    qpos = jnp.stack([qp[r, t] for t in range(T)])
    s = jnp.where(qpos[:, None] >= kidx, s, NEG_INF)

    m_prev = m_s[:, :1]
    l_prev = l_s[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    # a tile fully past the causal horizon leaves m_new at NEG_INF and
    # exp(s - m_new) = 1 everywhere — zero it (flash_attention's guard)
    p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    v = _tile_kv(v_ref, vs_ref, head, kv_mode, Dh, marker)
    acc[...] = acc[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_s[:, :1] = m_new
    l_s[:, :1] = l_new

    @pl.when(a == W - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)


def paged_attention_pallas(q, ck, cv, rows, q_pos, *,
                           kv_mode: str = "dense", block_size: int):
    """Drop-in for `paged_attention_reference` (tolerance parity)."""
    B, T, H, Dh = q.shape
    L = rows.shape[1]
    bs = int(block_size)
    if bs <= 0 or L % bs:
        raise ValueError(
            f"paged attention kernel needs rows ([{B}, {L}]) to cover "
            f"whole cache blocks of {bs}")
    W = L // bs
    # the gathered rows ARE table walks (programs.py builds them as
    # table*bs + arange(bs)); recover the table for scalar prefetch
    tables = (rows[:, ::bs] // bs).astype(jnp.int32)
    qp = q_pos.astype(jnp.int32)

    if kv_mode == "dense":
        marker = 0
        out_dtype = ck.dtype
        width = Dh

        def views(c):
            return (c.reshape(c.shape[0], H * Dh),)

        kv_specs = [
            pl.BlockSpec((bs, width),
                         lambda b, a, t, s: (_clamp(t[b // H, a]),
                                             jax.lax.rem(b, H))),
        ]
        operands = [*views(ck), *views(cv)]
        kv_specs = kv_specs * 2
    else:
        from ..runtime.comm.quant import qmax

        marker = -qmax(kv_mode) - 1
        out_dtype = jnp.float32
        pk, sk = ck
        pv, sv = cv
        width = pk.shape[-1]  # Dh (int8) or Dh // 2 (int4 nibbles)

        payload_spec = pl.BlockSpec(
            (bs, width), lambda b, a, t, s: (_clamp(t[b // H, a]),
                                             jax.lax.rem(b, H)))
        scale_spec = pl.BlockSpec(
            (bs, H), lambda b, a, t, s: (_clamp(t[b // H, a]), 0))
        kv_specs = [payload_spec, scale_spec, payload_spec, scale_spec]
        def bits(scales):  # fp16 -> its bit pattern, a free view
            return jax.lax.bitcast_convert_type(scales, jnp.uint16)

        operands = [pk.reshape(pk.shape[0], H * width), bits(sk),
                    pv.reshape(pv.shape[0], H * width), bits(sv)]

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, W),
        in_specs=[
            pl.BlockSpec((1, T, Dh), lambda b, a, t, s: (b, 0, 0)),
            *kv_specs,
        ],
        out_specs=pl.BlockSpec((1, T, Dh), lambda b, a, t, s: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, Dh), jnp.float32),
            pltpu.VMEM((T, 128), jnp.float32),
            pltpu.VMEM((T, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=Dh ** -0.5, bs=bs, W=W,
                          H=H, T=T, Dh=Dh, kv_mode=kv_mode,
                          marker=marker),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, T, Dh), out_dtype),
        compiler_params=_params(),
        interpret=pallas_backend.interpret(),
    )(tables, qp, qf, *operands)
    return out.reshape(B, H, T, Dh).transpose(0, 2, 1, 3)
