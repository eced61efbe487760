"""The gated delta rule of a decode step over the slots that are live,
and no others (models/qwen3_next.py `delta_step` is its oracle).

The walk is kernels/ssm.py's: the step's live list (`live_slots`: the
running slots in ascending order and their count) rides as scalar
prefetch, the grid's first size is the count, the state's block of a
grid step is slot `ids[j]`'s, and the state is aliased input to output —
a slot the grid never visits is neither read nor written.  The heads of
a block and the VMEM asked for are `ssm.head_tile`'s and ssm's limits.

The arithmetic is the oracle's, float32, term for term, per value head
with S [dk, dv] — dk on the sublanes, dv on the lanes:
S <- e^g S; u = beta (v - S^T k^); S <- S + k^ u^T; o = S^T q^.  S^T k^
and S^T q^ are sums over the sublanes of S times a column; k^ and q^
arrive with dk on the lanes, a block's heads at a time, and are turned
once a block.  e^g and beta are worked out before the call and come
broadcast over a row of dv lanes (a [slots, 5, heads, 128] operand: 4 %
of the state).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import pallas_backend
from .ssm import _STATE_BLOCK_BYTES, _STATE_REST, head_tile


def gdn_step_info(state) -> dict:
    """What the kernel registry may look at to choose the recurrence of
    a decode step over `state` [slots, heads, dk, dv] (an array or its
    shape and dtype)."""
    B, H, dk, dv = state.shape
    return {"slots": B, "heads": H, "key_dim": dk, "value_dim": dv,
            "itemsize": jnp.dtype(state.dtype).itemsize}


def _step_kernel(ids_ref, n_ref, rows_ref, s_ref, so_ref, o_ref):
    # k^ and q^ with a head's dk values down the sublanes
    kT, qT = rows_ref[0].T, rows_ref[1].T                     # [dk, th]
    v, beta, decay = rows_ref[2], rows_ref[3], rows_ref[4]    # [th, dv]
    for r in range(s_ref.shape[0]):
        k = kT[:, r:r + 1]                                    # [dk, 1]
        s = s_ref[r] * decay[r:r + 1, :]
        u = beta[r:r + 1, :] * (
            v[r:r + 1, :] - jnp.sum(s * k, axis=0, keepdims=True))
        s = s + k * u
        so_ref[r] = s
        o_ref[r:r + 1, :] = jnp.sum(s * qT[:, r:r + 1], axis=0,
                                    keepdims=True)


def gdn_step_pallas(q, k, v, g, beta, state, ids, n):
    """Drop-in for `delta_step` where `ids` [B] lists the `n` slots that
    step (`live_slots`): -> (o [B, H, dv], state).  A listed slot's state
    and o to float32 tolerance (the sums over dk in another order); any
    other slot's state is the input's, bit for bit, and its o zeros."""
    dv = v.shape[-1]
    wide = lambda t: jnp.broadcast_to(t[..., None], t.shape + (dv,))
    rows = jnp.stack([k, q, v, wide(beta), wide(jnp.exp(g))], axis=1)
    o, state = _step_live(rows, state, ids, jnp.reshape(n, (1,)),
                          interpret=pallas_backend.interpret())
    # a slot the grid did not visit has no o: zeros, whatever lies there
    listed = jnp.zeros((q.shape[0],), bool).at[ids].set(n > 0)
    return jnp.where(listed[:, None, None], o, 0.0), state


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_live(rows, state, ids, n, *, interpret):
    """The call, as a function of its own (a program that makes it in
    every layer lowers the kernel once).  Grid (place j of the list's n,
    tile of heads t); rows [B, 5, H, dk = dv]."""
    B, H, dk, dv = state.shape
    th = head_tile(H, dk, dv)
    block = pl.BlockSpec((None, th, dk, dv),
                         lambda j, t, ids, n: (ids[j], t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n[0], H // th),
        in_specs=[
            pl.BlockSpec((None, 5, th, dv),
                         lambda j, t, ids, n: (ids[j], 0, t, 0)),
            block,
        ],
        out_specs=[block,
                   pl.BlockSpec((None, th, dv),
                                lambda j, t, ids, n: (ids[j], t, 0))],
    )
    state, o = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), jnp.float32)],
        # operands count the two scalar-prefetch arrays: state -> state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
            vmem_limit_bytes=_STATE_BLOCK_BYTES + _STATE_REST),
        name="gdn_step_live",
        interpret=interpret,
    )(ids, n, rows, state)
    return o, state
