"""The Mamba-2 recurrence of a decode step over the slots that are live,
and no others (models/granite_hybrid.py `ssm_step` is its oracle).

A decode step takes one token for every running slot; a slot that is not
running has dt = 0 and the oracle hands its state back as it found it —
after reading and writing all of it.  Here the step's live list
(`live_slots`: the running slots in ascending order and their count)
rides as scalar prefetch, the grid walks the list's `n` places — the
grid's size is the count, so a step with 19 slots running makes 19 grid
steps and one with none makes none — and the state's block of a grid
step is slot `ids[j]`'s.  The state is aliased input to output: a slot
the grid never visits is neither read nor written and keeps its bytes.

The arithmetic is the oracle's, float32, term for term:
H <- H exp(dt A) + (dt x) (x) B per head, y = sum_N H C; exp(dt A) and
dt x are worked out before the call (they are [slots, heads] and
[slots, heads, head_dim]: a hundredth of a percent of the state), and
only the order of the sum over N may differ.  The state's tiles have
head_dim on the sublanes and N on the lanes, so the sum over N of a
(head, head_dim) row is a reduction along the lanes: the kernel takes
the rows 128 at a time, turns the [128, N] tile and adds its sublanes —
y then leaves with the rows on the lanes, as the caller holds it.

B and C may come a group of heads (`[B, G, N]`, head h reading group
h // (H / G); `[B, N]` is one group): a grid step takes the B and C of
the groups its tile of heads lies in, and each block of 128 rows reads
its group's — the registry's rule (`groups_fit`) holds a group's rows to
whole blocks and a tile to whole groups, or a group to whole tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import pallas_backend

# what the state's blocks of a grid step may take of VMEM — the one read
# and the one written, both pipeline buffers of each: 2 MiB a block — and
# what the kernel asks the compiler for beside them (dt x, exp(dt A), B
# and C, y and its own temporaries).  No more than it needs: in the
# decode program XLA's own prefetches want the rest, and a step of the
# Granite cell read 31.61 ms at 12 MiB asked, 31.69 at 24, 32.54 at 56;
# blocks of 1 MiB read the same as of 2 (PERF.md §6, PR 47)
_STATE_BLOCK_BYTES = 8 << 20
_STATE_REST = 4 << 20


def live_slots(active):
    """The slots of `active` [B] (bool, or a count of valid positions)
    that run: -> (ids [B] int32: the running slots in ascending order,
    the tail padded by repeating the last one — zeros where none runs —,
    their count int32): `moe/dropless.py::touched_list` over slots, each
    hit by its own token where it runs."""
    from ..moe.dropless import touched_list

    active = jnp.asarray(active)
    B = active.shape[0]
    return touched_list(jnp.arange(B, dtype=jnp.int32)[:, None], active > 0,
                        B)


def head_tile(heads: int, head_dim: int, state: int) -> int:
    """The heads of a slot's float32 state a grid step takes: all of
    them, or the largest divisor of `heads` whose block — read and
    written, double-buffered — fits `_STATE_BLOCK_BYTES`, one of whole
    tiles of 128 (head, head_dim) rows if there is one; 0 where none
    fits."""
    fits = [th for th in range(1, heads + 1) if heads % th == 0
            and 4 * th * head_dim * state * 4 <= _STATE_BLOCK_BYTES]
    return max([th for th in fits if th * head_dim % 128 == 0] or fits,
               default=0)


def groups_fit(heads: int, head_dim: int, groups: int, tile: int) -> bool:
    """Whether a tile of `tile` heads a grid step and blocks of 128
    (head, head_dim) rows fall on the bounds of `groups` groups of heads:
    a group's rows are whole blocks, and a tile is whole groups or a
    group whole tiles.  One group always fits."""
    per = heads // groups
    return groups == 1 or (per * head_dim % 128 == 0
                           and (tile % per == 0 or per % tile == 0))


def ssm_step_info(state, groups: int = 1) -> dict:
    """What the kernel registry may look at to choose the recurrence of
    a decode step over `state` [slots, heads, head_dim, N] (an array or
    its shape and dtype) with B and C in `groups` groups of heads."""
    B, H, P, N = state.shape
    return {"slots": B, "heads": H, "head_dim": P, "state": N,
            "groups": groups,
            "itemsize": jnp.dtype(state.dtype).itemsize}


def _step_kernel(ids_ref, n_ref, rows_ref, bc_ref, s_ref, so_ref, y_ref):
    # blocks of rows that read one group's B and C: all of the tile's
    # where it lies in one group
    per = s_ref.shape[0] // bc_ref.shape[0]
    # what multiplies a (head, head_dim) row of the state comes with the
    # rows on the lanes; the state has them on the sublanes: turned once
    # a block
    dtx, a = rows_ref[0].T, rows_ref[1].T                     # [128, R]
    for r in range(s_ref.shape[0]):
        Bm, Cm = bc_ref[r // per, 0:1, :], bc_ref[r // per, 1:2, :]  # [1, N]
        s = s_ref[r] * a[:, r:r + 1] + dtx[:, r:r + 1] * Bm
        so_ref[r] = s
        # the sum over N as a sum over the sublanes of the turned tile:
        # a reduction along the lanes, row by row, took longer than the
        # block's copies (PERF.md §6, PR 47)
        t = s * Cm                                            # [128, N]
        t = sum(t[:, k:k + 128] for k in range(0, t.shape[1], 128))
        y_ref[r:r + 1, :] = jnp.sum(t.T, axis=0, keepdims=True)


def ssm_step_pallas(x, Bm, Cm, dt, A, state, ids, n):
    """Drop-in for `ssm_step` (Bm, Cm [B, N]; or [B, G, N], a B and a C
    a group of heads: `by_group(ssm_step, G)`) where `ids` [B] lists the
    `n` slots with dt != 0 (`live_slots`): -> (y [B, H, P], state).  A
    listed slot's state and y to float32 tolerance (the sum over N in
    another order); any other slot's state is the input's, bit for bit,
    and its y zeros."""
    y, state = _step_live(jnp.exp(dt * A), dt[:, :, None] * x, Bm, Cm,
                          state, ids, jnp.reshape(n, (1,)),
                          interpret=pallas_backend.interpret())
    # a slot the grid did not visit has no y: zeros, whatever lies there
    listed = jnp.zeros((x.shape[0],), bool).at[ids].set(n > 0)
    return jnp.where(listed[:, None, None], y, 0.0), state


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_live(a, dtx, Bm, Cm, state, ids, n, *, interpret):
    """The call, as a function of its own (a program that makes it in
    every layer lowers the kernel once).  Grid (place j of the list's
    n, tile of heads t); a [B, H], dtx [B, H, P].  Inside, a slot's state
    is `[H * P / 128, 128, N]` — its (head, head_dim) rows 128 at a
    time, the same bytes — and dt x, exp(dt A) and y are the rows' values,
    `[.., H * P / 128, 128]`."""
    B, H, P, N = state.shape
    th = head_tile(H, P, N)
    G = Bm.shape[1] if Bm.ndim == 3 else 1
    gt = max(1, th * G // H)       # groups a tile of heads lies in
    # (the interpreter takes any number of rows as a block: a tile's, or
    # those of each of its groups)
    Q = 128 if th * P % 128 == 0 else th * P // gt
    nt, R = H // th, th * P // Q
    rows = jnp.stack([dtx, jnp.broadcast_to(a[:, :, None], dtx.shape)], 1)
    rows = rows.reshape(B, 2, nt, R, Q).swapaxes(1, 2)
    # [B, G, 2, N]: a group's B and C side by side; a grid step takes
    # the gt groups its tile of heads lies in
    bc = jnp.stack([Bm.reshape(B, G, N), Cm.reshape(B, G, N)], axis=2)

    slot = lambda *zeros: lambda j, t, ids, n: (ids[j], t, *zeros)
    block = pl.BlockSpec((None, R, Q, N), slot(0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n[0], nt),
        in_specs=[
            pl.BlockSpec((None, None, 2, R, Q), slot(0, 0, 0)),
            pl.BlockSpec((None, gt, 2, N), lambda j, t, ids, n: (
                ids[j], t * th * G // (H * gt), 0, 0)),
            block,
        ],
        out_specs=[block, pl.BlockSpec((None, None, R, Q), slot(0, 0))],
    )
    flat, y = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nt * R, Q, N), state.dtype),
                   jax.ShapeDtypeStruct((B, nt, R, Q), jnp.float32)],
        # operands count the two scalar-prefetch arrays: state -> state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
            vmem_limit_bytes=_STATE_BLOCK_BYTES + _STATE_REST),
        name="ssm_step_live",
        interpret=interpret,
    )(ids, n, rows, bc, state.reshape(B, nt * R, Q, N))
    return y.reshape(B, H, P), flat.reshape(state.shape)
