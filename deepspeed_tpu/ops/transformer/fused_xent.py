"""Fused projection + softmax cross-entropy as Pallas TPU kernels.

The LM-head loss is the last big HBM consumer in the training step: even
the chunked XLA path (models/gpt.py::_softmax_xent_from_hidden) writes
each [rows, V] logits chunk to HBM once in forward and recomputes it in
backward. These kernels stream vocab blocks through VMEM with an online
logsumexp — logits NEVER exist in HBM:

  forward   grid (row_blk, v_blk):   lse/label-logit accumulators in VMEM
  backward  dx: grid (row_blk, v_blk) accumulating dl @ w_blk^T
            dw: grid (v_blk, row_blk) accumulating x_blk^T @ dl
  where dl = g * valid * (exp(logit - lse) - onehot) is re-formed
  blockwise from the saved per-row lse (flash-attention-style recompute
  applied to the classifier).

Wire cost per step: read x twice, read w three times, write dx + dw —
~2 GB at GPT-2-small shapes vs ~5-6 GB for the chunked XLA form.
Opt-in via GPTConfig.loss_impl="pallas" until measured on a real chip;
not valid under vocab-parallel TP (the online lse is row-global here).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas_backend

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_V = 512
NEG_INF = -1e30


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY))


# ---------------------------------------------------------------------------
# forward: per-row (logsumexp, label logit)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, lab_ref, lse_ref, ll_ref, m_s, l_s, ll_s, *,
                bv, nv):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        ll_s[:] = jnp.zeros_like(ll_s)

    logits = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_prev = m_s[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    l_s[:, :1] = l_s[:, :1] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_s[:, :1] = m_new
    vidx = j * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = (vidx == lab_ref[...][:, :1])
    ll_s[:, :1] += jnp.sum(jnp.where(onehot, logits, 0.0), axis=1,
                           keepdims=True)

    @pl.when(j == nv - 1)
    def _finish():
        lse_ref[...] = jnp.broadcast_to(
            m_s[:, :1] + jnp.log(l_s[:, :1]), lse_ref.shape)
        ll_ref[...] = jnp.broadcast_to(ll_s[:, :1], ll_ref.shape)


def _fwd(x, w, labels, br, bv) -> Tuple[jax.Array, jax.Array]:
    N, D = x.shape
    V = w.shape[1]
    if N % br or V % bv:
        # the grids are N // br x V // bv, here and in the backward: a
        # remainder would be dropped without a word
        raise ValueError(
            f"fused CE blocks ({br}, {bv}) do not divide N={N}, V={V} "
            f"(pick_blocks chooses ones that do)")
    nr, nv = N // br, V // bv
    lse, ll = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bv), lambda i, j: (0, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 128), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 128), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 128), jnp.float32),
            jax.ShapeDtypeStruct((N, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=pallas_backend.interpret(),
    )(x, w, labels[:, None])
    return lse[:, 0], ll[:, 0]


# ---------------------------------------------------------------------------
# backward: dl = coef * (softmax - onehot), streamed
# ---------------------------------------------------------------------------

def _dl_block(x_ref, w_ref, lab_ref, lse_ref, coef_ref, j, bv):
    logits = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[...][:, :1])
    vidx = j * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = (vidx == lab_ref[...][:, :1]).astype(jnp.float32)
    return (p - onehot) * coef_ref[...][:, :1]


def _dx_kernel(x_ref, w_ref, lab_ref, lse_ref, coef_ref, dx_ref, acc, *,
               bv, nv):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    dl = _dl_block(x_ref, w_ref, lab_ref, lse_ref, coef_ref, j, bv)
    acc[:] += jax.lax.dot_general(
        dl, w_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _finish():
        dx_ref[...] = acc[:].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, lab_ref, lse_ref, coef_ref, dw_ref, acc, *,
               bv, nr):
    i = pl.program_id(1)
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    dl = _dl_block(x_ref, w_ref, lab_ref, lse_ref, coef_ref, j, bv)
    acc[:] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), dl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nr - 1)
    def _finish():
        dw_ref[...] = acc[:].astype(dw_ref.dtype)


def _bwd(br, bv, res, g):
    x, w, labels, valid, lse = res
    N, D = x.shape
    V = w.shape[1]
    nr, nv = N // br, V // bv
    coef = (g * valid.astype(jnp.float32))[:, None]  # [N, 1]
    lab = labels[:, None]

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, bv=bv, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bv), lambda i, j: (0, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 128), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((br, D), jnp.float32)],
        compiler_params=_params(),
        interpret=pallas_backend.interpret(),
    )(x, w, lab, jnp.broadcast_to(lse[:, None], (N, 128)), coef)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, bv=bv, nr=nr),
        grid=(nv, nr),
        in_specs=[
            pl.BlockSpec((br, D), lambda j, i: (i, 0)),
            pl.BlockSpec((D, bv), lambda j, i: (0, j)),
            pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((br, 128), lambda j, i: (i, 0)),
            pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((D, bv), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((D, V), w.dtype),
        scratch_shapes=[pltpu.VMEM((D, bv), jnp.float32)],
        compiler_params=_params(),
        interpret=pallas_backend.interpret(),
    )(x, w, lab, jnp.broadcast_to(lse[:, None], (N, 128)), coef)
    return dx, dw, None, None


def pick_blocks(n_rows: int, vocab: int):
    """(block_rows, block_v) that divide the shapes, or None.  Vocab
    50304 = 393*128 takes 384; the padded-to-128 GPT-2 family always
    has a lane-aligned divisor."""
    br = next((b for b in (256, 128) if n_rows % b == 0), None)
    bv = next((b for b in (512, 448, 384, 256, 128) if vocab % b == 0),
              None)
    return (br, bv) if br and bv else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_softmax_xent_sum(x, w, labels, valid,
                           block_rows: int = DEFAULT_BLOCK_ROWS,
                           block_v: int = DEFAULT_BLOCK_V):
    """Sum over valid rows of (logsumexp(x @ w) - (x @ w)[label]).

    x [N, D], w [D, V], labels [N] int32 (in-range), valid [N] bool.
    Requires N % block_rows == 0 and V % block_v == 0. NOT valid when w
    is vocab-sharded (lse is computed row-globally in-kernel)."""
    lse, ll = _fwd(x, w, labels, block_rows, block_v)
    return jnp.sum(jnp.where(valid, lse - ll, 0.0))


def _fwd_rule(x, w, labels, valid, block_rows, block_v):
    lse, ll = _fwd(x, w, labels, block_rows, block_v)
    out = jnp.sum(jnp.where(valid, lse - ll, 0.0))
    return out, (x, w, labels, valid, lse)


def _bwd_rule(block_rows, block_v, res, g):
    return _bwd(block_rows, block_v, res, g)


fused_softmax_xent_sum.defvjp(_fwd_rule, _bwd_rule)
