"""Block-sparse FLASH attention — Pallas TPU kernels driven by a static
SparsityConfig layout.

Reference: the Triton block-sparse kernel family
(/root/reference/deepspeed/ops/sparse_attention/matmul.py:749 SDD/DSD/DDS,
softmax.py:315, trsrc/*.tr) behind sparse_self_attention.py:14. The
XLA path (sparse_attention.py) gathers key blocks and materialises
[.., W, blk, blk] score tiles in HBM; this kernel streams them: each
(batch·head, q-block) program walks ONLY its layout row's active k-blocks
(a scalar-prefetched index table — the TPU analogue of the reference's
LUTs from csrc/sparse_attention/utils.cpp) with an online-softmax
accumulator in VMEM. HBM traffic is O(S·W·blk) with no score tensor at
all, and every tile is MXU-shaped.

Tables: layout [H, nq, nk] ->
  fwd  table [H, nq, W]  (active k-block ids, -1 padded)
  bwd  table [H, nk, Wq] (reverse: q-blocks touching each k-block)
Both ride pltpu.PrefetchScalarGridSpec scalar prefetch, so BlockSpec
index maps select the k/v (or q/do) block to DMA per grid step; padded
slots clamp to block 0 and are masked in-kernel.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas_backend
from ..transformer.flash_attention import (_compiler_params, _keep_mask,
                                           derive_seed)

NEG_INF = -1e30


def layout_tables(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """[H, nq, nk] 0/1 -> (fwd [H, nq, W], rev [H, nk, Wq]), -1 padded."""
    layout = np.asarray(layout)
    H, nq, nk = layout.shape
    W = max(1, int(layout.sum(-1).max()))
    Wq = max(1, int(layout.sum(-2).max()))
    fwd = np.full((H, nq, W), -1, np.int32)
    rev = np.full((H, nk, Wq), -1, np.int32)
    for h in range(H):
        for i in range(nq):
            nz = np.nonzero(layout[h, i])[0]
            fwd[h, i, :len(nz)] = nz
        for j in range(nk):
            nz = np.nonzero(layout[h, :, j])[0]
            rev[h, j, :len(nz)] = nz
    return fwd, rev


def _causal_mask(s, qi, kj, blk):
    qidx = qi * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kidx = kj * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qidx >= kidx, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(tbl, seed, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s,
                l_s, *, scale, causal, blk, W, H, rate):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    a = pl.program_id(2)
    h = jax.lax.rem(b, H)
    kj = tbl[h, qi, a]

    @pl.when(a == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    @pl.when(kj >= 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, kj, blk)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:, :1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if rate > 0.0:
            # same global-index hash as the dense flash kernel: the mask
            # depends on token coordinates (via the layout table), so the
            # dq/dkv walks regenerate identical tiles
            p = p * _keep_mask(seed[0], b, qi * blk, kj * blk, blk, blk,
                               rate)
        acc[:] = acc[:] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_s[:, :1] = m_new

    @pl.when(a == W - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            jnp.where(l == 0.0, NEG_INF, m_s[:, :1] + jnp.log(safe_l)),
            lse_ref[0].shape)


def _fwd(q, k, v, tbl, seed, causal, scale, blk, H, rate):
    BH, S, D = q.shape
    nq = S // blk
    W = tbl.shape[-1]

    def clamp(j):
        return jnp.maximum(j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq, W),
        in_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i, a, t, sd: (b, i, 0)),
            pl.BlockSpec((1, blk, D),
                         lambda b, i, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), i, a]), 0)),
            pl.BlockSpec((1, blk, D),
                         lambda b, i, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), i, a]), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i, a, t, sd: (b, i, 0)),
            pl.BlockSpec((1, blk, 128), lambda b, i, a, t, sd: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, D), jnp.float32),
            pltpu.VMEM((blk, 128), jnp.float32),
            pltpu.VMEM((blk, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               blk=blk, W=W, H=H, rate=rate)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=pallas_backend.interpret(),
    )(tbl, seed, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(tbl, seed, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, scale, causal, blk, W, H, rate):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    a = pl.program_id(2)
    h = jax.lax.rem(b, H)
    kj = tbl[h, qi, a]

    @pl.when(a == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(kj >= 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, kj, blk)
        p = jnp.exp(s - lse_ref[0][:, :1])
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            dp = dp * _keep_mask(seed[0], b, qi * blk, kj * blk, blk, blk,
                                 rate)
        ds = p * (dp - delta_ref[0][:, :1])
        dq_acc[:] += scale * jnp.dot(ds.astype(k_ref.dtype), k_ref[0],
                                     preferred_element_type=jnp.float32)

    @pl.when(a == W - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(tbl, seed, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, blk, Wq,
                H, rate):
    b = pl.program_id(0)
    kjg = pl.program_id(1)
    a = pl.program_id(2)
    h = jax.lax.rem(b, H)
    qi = tbl[h, kjg, a]

    @pl.when(a == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(qi >= 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, kjg, blk)
        p = jnp.exp(s - lse_ref[0][:, :1])
        do = do_ref[0].astype(jnp.float32)
        if rate > 0.0:
            mask = _keep_mask(seed[0], b, qi * blk, kjg * blk, blk, blk,
                              rate)
            pd = p * mask
        else:
            mask = None
            pd = p
        dv_acc[:] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if mask is not None:
            dp = dp * mask
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[:] += scale * jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(a == Wq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, scale, blk, H, rate, tables, res, dout):
    fwd_tbl, rev_tbl = tables
    q, k, v, seed, out, lse = res
    BH, S, D = q.shape
    nq = S // blk
    W = fwd_tbl.shape[-1]
    Wq = rev_tbl.shape[-1]
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    def clamp(j):
        return jnp.maximum(j, 0)

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq, W),
        in_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i, a, t, sd: (b, i, 0)),
            pl.BlockSpec((1, blk, D),
                         lambda b, i, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), i, a]), 0)),
            pl.BlockSpec((1, blk, D),
                         lambda b, i, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), i, a]), 0)),
            pl.BlockSpec((1, blk, D), lambda b, i, a, t, sd: (b, i, 0)),
            pl.BlockSpec((1, blk, 128), lambda b, i, a, t, sd: (b, i, 0)),
            pl.BlockSpec((1, blk, 128), lambda b, i, a, t, sd: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, D), lambda b, i, a, t, sd: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((blk, D), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, blk=blk,
                          W=W, H=H, rate=rate),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        compiler_params=_compiler_params(),
        interpret=pallas_backend.interpret(),
    )(fwd_tbl, seed, q, k, v, dout, lse, delta)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq, Wq),
        in_specs=[
            pl.BlockSpec((1, blk, D),
                         lambda b, j, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), j, a]), 0)),
            pl.BlockSpec((1, blk, D), lambda b, j, a, t, sd: (b, j, 0)),
            pl.BlockSpec((1, blk, D), lambda b, j, a, t, sd: (b, j, 0)),
            pl.BlockSpec((1, blk, D),
                         lambda b, j, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), j, a]), 0)),
            pl.BlockSpec((1, blk, 128),
                         lambda b, j, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), j, a]), 0)),
            pl.BlockSpec((1, blk, 128),
                         lambda b, j, a, t, sd: (
                             b, clamp(t[jax.lax.rem(b, H), j, a]), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, D), lambda b, j, a, t, sd: (b, j, 0)),
            pl.BlockSpec((1, blk, D), lambda b, j, a, t, sd: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, D), jnp.float32),
            pltpu.VMEM((blk, D), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, blk=blk,
                          Wq=Wq, H=H, rate=rate),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        compiler_params=_compiler_params(),
        interpret=pallas_backend.interpret(),
    )(rev_tbl, seed, q, k, v, dout, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (BSHD) with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_sparse_bhsd(q, k, v, seed, fwd_tbl, rev_tbl, causal, scale, blk,
                       H, rate):
    out, _ = _fwd(q, k, v, jnp.asarray(fwd_tbl), seed, causal, scale, blk,
                  H, rate)
    return out


def _fwd_rule(q, k, v, seed, fwd_tbl, rev_tbl, causal, scale, blk, H, rate):
    out, lse = _fwd(q, k, v, jnp.asarray(fwd_tbl), seed, causal, scale, blk,
                    H, rate)
    return out, (q, k, v, seed, out, lse)


def _bwd_rule(fwd_tbl, rev_tbl, causal, scale, blk, H, rate, res, dout):
    return (*_bwd(causal, scale, blk, H, rate,
                  (jnp.asarray(fwd_tbl), jnp.asarray(rev_tbl)), res, dout),
            None)


_flash_sparse_bhsd.defvjp(_fwd_rule, _bwd_rule)


def flash_sparse_attention(q, k, v, layout: np.ndarray, block: int,
                           causal: bool = False,
                           scale: Optional[float] = None,
                           dropout_rate: float = 0.0,
                           dropout_rng=None):
    """Block-sparse flash attention over [B, S, H, D] (BSHD).

    layout: STATIC numpy [H, S/block, S/block] 0/1 (SparsityConfig
    layouts are block-granular; `causal=True` additionally token-masks
    the diagonal blocks). The kernel tiles at the LAYOUT's block size —
    SparsityConfig blocks of 128 map 1:1 onto MXU tiles; smaller layout
    blocks still run (interpret/compat) but waste lanes.

    dropout_rate > 0 with a dropout_rng applies probability dropout
    in-kernel — the same global-index hash mask as the dense flash
    kernel (ops/transformer/flash_attention.py), regenerated in both
    backward walks, never materialised at [S, S].
    """
    B, S, Hh, D = q.shape
    nb = S // block
    assert S % block == 0, (S, block)
    layout = np.asarray(layout)
    assert layout.shape == (Hh, nb, nb), (layout.shape, (Hh, nb, nb))
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    fwd_tbl, rev_tbl = layout_tables(layout)
    scale = (D ** -0.5) if scale is None else scale
    seed, rate = derive_seed(dropout_rate, dropout_rng)
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(B * Hh, S, D)
    # hashable static tables for the custom-vjp nondiff args
    fwd_key = tuple(map(tuple, fwd_tbl.reshape(Hh * nb, -1)))
    rev_key = tuple(map(tuple, rev_tbl.reshape(Hh * nb, -1)))
    out = _flash_sparse_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v), seed,
        _Table(fwd_key, (Hh, nb, fwd_tbl.shape[-1])),
        _Table(rev_key, (Hh, nb, rev_tbl.shape[-1])),
        causal, scale, block, Hh, rate)
    return out.reshape(B, Hh, S, D).transpose(0, 2, 1, 3)


class _Table:
    """Hashable static wrapper so layout tables can ride custom_vjp
    nondiff_argnums; __array__ lets jnp.asarray recover the int32 data."""

    def __init__(self, key, shape):
        self._key = key
        self._shape = shape

    def __hash__(self):
        return hash((self._key, self._shape))

    def __eq__(self, other):
        return isinstance(other, _Table) and self._key == other._key and \
            self._shape == other._shape

    def __array__(self, dtype=None):
        return np.asarray(self._key, np.int32).reshape(self._shape)
