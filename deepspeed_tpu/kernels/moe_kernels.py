"""Pallas MoE kernels: the routed product of a call of few rows over the
experts its live rows touched (`touched_experts`, moe/dropless.py — at
the end of this file, and the one the chip runs), and the sort-based
dispatch/combine of the trainer's layer (op 3, moe/dispatch.py):

The jnp oracles move tokens with a scatter-add (`sorted_dispatch_ref`)
and a gated gather (`sorted_combine_ref`).  On TPU the scatter lowers
to a serialized HBM update stream; these kernels re-express both
directions as per-slot / per-token GATHERS driven by scalar-prefetched
index tables, which Mosaic turns into plain async block copies:

* dispatch — the oracle's kept destinations are UNIQUE (capacity
  assignment), so the scatter has an exact inverse permutation.  A tiny
  jnp prologue inverts `dest` into `src_tok[slot] -> token | -1`; the
  kernel then copies `x[src_tok[s]]` into slot `s` (zeros when empty).
  Parity is bit-exact: every slot is a verbatim row copy or zeros,
  matching add-into-zeros.
* combine — slot sources `src[a, n]` (the trash row E*C when dropped)
  and fp32 gate weights ride SMEM; each token accumulates its k expert
  rows in ascending assignment order — the same term order as the
  oracle's axis-0 sum.  Parity is tolerance-bounded at ~1 ulp: the
  accumulator's multiply-add may fuse to an FMA where the oracle's
  separate mul/sum rounds twice.

Both oracles are vmapped over batch rows by callers; these wrappers
are shaped identically so `dispatch("moe_dispatch", ...)` drops in
under the same vmap.

On the chip: NOT selected.  Both kernels move one `(1, D)` row per grid
step, a block the Pallas TPU lowering refuses (its last two dims must
divide by 8 and 128), and a gather of arbitrary rows has no 8-row tile
to move instead: the rewrite is a manual row DMA from HBM.  Until then
the registry (`MoEDispatchOp.is_compatible`) refuses them by name and
they run under the interpreter only (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import pallas_backend
from .expert_form import expert_hidden


def _clamp(i):
    return jnp.maximum(i, 0)


# ---------------------------------------------------------------------------
# dispatch: tokens -> [E, C, D] capacity buckets
# ---------------------------------------------------------------------------


def _dispatch_kernel(tok_ref, x_ref, o_ref):
    s = pl.program_id(0)
    # empty slots (tok == -1) read a clamped dummy row; the where zeroes it
    o_ref[...] = jnp.where(tok_ref[s] >= 0, x_ref[...],
                           jnp.zeros_like(o_ref))


def sorted_dispatch_pallas(x, eidx, pos, keep, num_experts: int,
                           capacity: int):
    """Drop-in for `sorted_dispatch_ref` (bit-exact)."""
    k, N = eidx.shape
    D = x.shape[-1]
    E, C = num_experts, capacity
    flat_keep = keep.reshape(-1)
    dest = jnp.where(flat_keep, eidx.reshape(-1) * C + pos.reshape(-1),
                     E * C)
    # invert the (unique-per-slot) scatter: slot -> assignment -> token.
    # assignment a carries token a % N (the oracle's tiled gather order)
    slot_a = jnp.full((E * C + 1,), -1, jnp.int32).at[dest].set(
        jnp.arange(k * N, dtype=jnp.int32))[:E * C]
    src_tok = jnp.where(slot_a >= 0,
                        jax.lax.rem(slot_a, jnp.int32(N)),
                        jnp.int32(-1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E * C,),
        in_specs=[
            pl.BlockSpec((1, D), lambda s, tok: (_clamp(tok[s]), 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda s, tok: (s, 0)),
    )
    buf = pl.pallas_call(
        _dispatch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E * C, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=pallas_backend.interpret(),
    )(src_tok, x)
    return buf.reshape(E, C, D)


# ---------------------------------------------------------------------------
# combine: gated gather back to [N, D]
# ---------------------------------------------------------------------------


def _combine_kernel(src_ref, w_ref, flat_ref, o_ref, acc, *, k, N):
    n = pl.program_id(0)
    a = pl.program_id(1)

    @pl.when(a == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # dropped assignments point src at the zero trash row AND carry
    # w == 0, so the term vanishes exactly like the oracle's
    acc[...] = acc[...] + flat_ref[...].astype(jnp.float32) * w_ref[a, n]

    @pl.when(a == k - 1)
    def _finish():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def sorted_combine_pallas(expert_out, eidx, gate, pos, keep):
    """Drop-in for `sorted_combine_ref` (~1-ulp tolerance parity)."""
    E, C, D = expert_out.shape
    k, N = eidx.shape
    flat = jnp.concatenate(
        [expert_out.reshape(E * C, D),
         jnp.zeros((1, D), expert_out.dtype)])
    src = jnp.where(keep.reshape(-1),
                    eidx.reshape(-1) * C + pos.reshape(-1),
                    E * C).astype(jnp.int32)
    # the oracle weights in expert_out's dtype; replicate the rounding
    # by casting gate*keep through that dtype before the fp32 multiply
    w = (gate * keep).astype(expert_out.dtype).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N, k),
        in_specs=[
            pl.BlockSpec((1, D), lambda n, a, src, w: (src[a * N + n], 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda n, a, src, w: (n, 0)),
        scratch_shapes=[pltpu.VMEM((1, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_combine_kernel, k=k, N=N),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), expert_out.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=pallas_backend.interpret(),
    )(src, w, flat)


# ---------------------------------------------------------------------------
# touched experts: the routed product of a call of few rows
# ---------------------------------------------------------------------------

# what the weight tiles a grid step reads — one of each of an expert's
# `matrices` (expert_form.py `expert_matrices`: gate, up and down, or
# up and down) — may take of VMEM, both pipeline buffers counted; the
# kernel asks the compiler for this much and `_TOUCHED_REST` for x, the
# weights, the accumulator and its own temporaries (a v5e's VMEM is
# 128 MiB, its scoped default 16)
_TOUCHED_TILE_BYTES = 48 << 20
_TOUCHED_REST = 16 << 20
# the matrices in front of an expert's `down`, in the order both kernels
# take them: those of these its form has
_FRONT = ("gate", "up")


def _front(experts) -> dict:
    """The matrices of `experts` that multiply the rows."""
    return {k: experts[k] for k in _FRONT if k in experts}


def _turned(expert_dim: int) -> bool:
    """Whether the front matrices go to the kernel turned, `[E, F, D]`:
    where an expert's F columns are not whole 128-lane tiles the chip
    holds a `[E, D, F]` array with D on the lanes (its layout {1,2,0}),
    and a kernel that asked for it as it is written would be handed a
    copy of every expert's matrix, every call (PERF.md section 6,
    PR 61); turned, the same bytes are the array the kernel asks for."""
    return expert_dim % 128 != 0


def _hidden(x, front_refs, turned: bool):
    """`expert_hidden` of the rows x over a grid step's tiles of the
    front matrices — `[D, tf]`, or `[tf, D]` where they come turned — at
    x's dtype (the roundings of `experts_weighted` and `grouped_ffn`:
    operands at the weights' dtype, float32 sums, the hidden values
    rounded before `down`)."""
    refs = dict(zip(_FRONT[-len(front_refs):], front_refs))
    dims = (((1,), (1 if turned else 0,)), ((), ()))
    return expert_hidden(
        lambda ref: jax.lax.dot_general(
            x, ref[...], dims, preferred_element_type=jnp.float32),
        refs).astype(x.dtype)


def touched_tile(model_dim: int, expert_dim: int, itemsize: int,
                 matrices: int = 3) -> int:
    """The columns of an expert's front matrices (rows of its `down`) a
    grid step takes: the largest divisor of `expert_dim` in whole
    128-lane tiles — or all of it — whose `matrices` tiles (one of each
    of the expert's operands: 3 of a SiLU-gated expert, 2 of a relu2
    one), double-buffered, fit `_TOUCHED_TILE_BYTES`; 0 where none
    does."""
    fits = lambda tf: 2 * matrices * model_dim * tf * itemsize \
        <= _TOUCHED_TILE_BYTES
    if fits(expert_dim):
        return expert_dim
    return max((tf for tf in range(128, expert_dim, 128)
                if expert_dim % tf == 0 and fits(tf)), default=0)


def _touched_kernel(ids_ref, n_ref, x_ref, w_ref, *refs, turned):
    *front, down_ref, o_ref = refs
    j, f = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (f == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < n_ref[0])
    def _expert():
        out = jnp.dot(_hidden(x_ref[...], front, turned), down_ref[...],
                      preferred_element_type=jnp.float32)
        w = w_ref[...]                                    # (T, E)
        mine = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) == ids_ref[j]
        o_ref[...] += out * jnp.sum(jnp.where(mine, w, 0.0), axis=1,
                                    keepdims=True)


def touched_experts_pallas(x, experts, w, ids, n):
    """Drop-in for `moe/dropless.py::experts_weighted` where `ids` [E]
    lists the `n` experts with a weight in w [T, E] (`touched_list`):
    x [T, D] -> [T, D] float32, tolerance parity (the sum over experts
    and over an expert's column tiles is taken in another order)."""
    return _touched(x.astype(experts["up"].dtype), _front(experts),
                    experts["down"], w, ids, jnp.reshape(n, (1,)),
                    interpret=pallas_backend.interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _touched(x, front, down, w, ids, n, *, interpret):
    """The call, as a function of its own (a program that makes it in
    every layer lowers the kernel once).  Grid (place j in the list,
    column tile f); the weight tiles' index maps read `ids[j]`, and
    from place `n` on stay on the last tile read, so that the pipeline
    copies nothing more; the output block is the float32 accumulator."""
    T, D = x.shape
    E, F, _ = down.shape
    tf = touched_tile(D, F, down.dtype.itemsize, len(front) + 1)
    nf = F // tf
    rows = -(-T // 16) * 16            # whole tiles of bf16 rows
    if rows != T:
        x = jnp.pad(x, ((0, rows - T), (0, 0)))
        w = jnp.pad(w, ((0, rows - T), (0, 0)))

    def tile(j, f, ids, n):      # (expert, column tile) of a grid step
        return ids[j], jnp.where(j < n[0], f, nf - 1)

    def cols_of(*step):
        expert, t = tile(*step)
        return expert, 0, t

    whole = lambda j, f, ids, n: (0, 0)
    rows_of = pl.BlockSpec((None, tf, D), lambda *step: (*tile(*step), 0))
    turned = _turned(F)
    if turned:
        front = {k: jnp.swapaxes(w_, 1, 2) for k, w_ in front.items()}
    cols = rows_of if turned else pl.BlockSpec((None, D, tf), cols_of)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, nf),
        in_specs=[
            pl.BlockSpec((rows, D), whole),
            pl.BlockSpec((rows, E), whole),
            *[cols] * len(front),
            rows_of,
        ],
        out_specs=pl.BlockSpec((rows, D), whole),
    )
    out = pl.pallas_call(
        functools.partial(_touched_kernel, turned=turned),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
            vmem_limit_bytes=_TOUCHED_TILE_BYTES + _TOUCHED_REST),
        name="touched_experts",
        interpret=interpret,
    )(ids, n, x, w, *front.values(), down)
    return out[:T]


# ---------------------------------------------------------------------------
# grouped experts: the routed product of a slab of rows sorted by expert
# ---------------------------------------------------------------------------

# rows of the slab one product of the walk takes: a window that starts at
# an expert's first row, rounded down to whole bf16 tiles, and covers its
# range in one step wherever the range is under the ridge (the weights
# pass through the MXU once a window, whatever rows it holds)
GROUPED_WINDOW = 128
# what the kernel may ask of a v5e's 128 MiB of VMEM: the slab and its
# float32 result, held once, the weight tiles (one of each of an
# expert's matrices) in both pipeline buffers and a window's temporaries
_GROUPED_VMEM = 100 << 20
_WINDOW_ALIGN = 16                  # whole tiles of bf16 rows


def _whole_tiles(rows: int) -> int:
    return -(-rows // _WINDOW_ALIGN) * _WINDOW_ALIGN


def grouped_vmem(rows: int, model_dim: int, tile: int, itemsize: int,
                 matrices: int = 3) -> int:
    """Bytes of VMEM the walk over a slab of `rows` rows needs at a
    column tile of `tile` of an expert's `matrices` operands: what
    `grouped_tile` fits and the call asks the compiler for."""
    # a window of room behind the slab's rows for the last expert's
    slab = (_whole_tiles(rows) + GROUPED_WINDOW) * model_dim * (itemsize + 4)
    weights = 2 * matrices * model_dim * tile * itemsize
    window = GROUPED_WINDOW * (2 * model_dim + matrices * tile) * 4
    return slab + weights + window + (4 << 20)


def grouped_tile(rows: int, model_dim: int, expert_dim: int,
                 itemsize: int, matrices: int = 3) -> int:
    """The columns of an expert's front matrices (rows of its `down`) a
    grid step of the walk over a slab of `rows` rows takes: the largest
    divisor of `expert_dim` in whole 128-lane tiles — or all of it —
    that `grouped_vmem` fits into `_GROUPED_VMEM`; 0 where none does."""
    fits = lambda tf: grouped_vmem(rows, model_dim, tf, itemsize,
                                   matrices) <= _GROUPED_VMEM
    if fits(expert_dim):
        return expert_dim
    return max((tf for tf in range(128, expert_dim, 128)
                if expert_dim % tf == 0 and fits(tf)), default=0)


def _grouped_kernel(off_ref, src_ref, col_ref, x_hbm, *refs, rows, turned):
    *front, down_ref, o_hbm, x_ref, acc_ref, sem = refs
    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _load():
        copy = pltpu.make_async_copy(x_hbm, x_ref.at[pl.ds(0, rows)], sem)
        copy.start()
        acc_ref[...] = jnp.zeros_like(acc_ref)
        copy.wait()

    lo, hi = off_ref[e], off_ref[e + 1]
    start = lo // _WINDOW_ALIGN * _WINDOW_ALIGN

    def window(i, carry):
        r0 = pl.multiple_of(start + i * GROUPED_WINDOW, _WINDOW_ALIGN)
        at = pl.ds(r0, GROUPED_WINDOW)
        out = jnp.dot(_hidden(x_ref[at, :], front, turned), down_ref[...],
                      preferred_element_type=jnp.float32)
        row = r0 + jax.lax.broadcasted_iota(
            jnp.int32, (GROUPED_WINDOW, 1), 0)
        # a row of another expert, or of none, is never written: what
        # it holds (anything, past the slab's end) stays out of the sum
        acc_ref[at, :] += jnp.where((row >= lo) & (row < hi), out, 0.0)
        return carry

    jax.lax.fori_loop(
        0, jnp.where(hi > lo, -(-(hi - start) // GROUPED_WINDOW), 0),
        window, 0)

    @pl.when((e == pl.num_programs(0) - 1) & (f == pl.num_programs(1) - 1))
    def _store():
        copy = pltpu.make_async_copy(acc_ref.at[pl.ds(0, rows)], o_hbm, sem)
        copy.start()
        copy.wait()


def grouped_experts_pallas(xs, experts, offsets):
    """Drop-in for `moe/dropless.py::grouped_ffn`: xs [C, D], the rows of
    a slab sorted by expert, expert e's at `offsets[e]` ..
    `offsets[e + 1]` (offsets [E + 1] int32, ascending, within 0 .. C)
    -> [C, D] float32, each row through its expert's FFN and 0 from
    `offsets[E]` on; tolerance parity (an expert's column tiles are
    summed in another order)."""
    return _grouped(xs.astype(experts["up"].dtype), _front(experts),
                    experts["down"], offsets,
                    interpret=pallas_backend.interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped(xs, front, down, offsets, *, interpret):
    """The call, as a function of its own (a program that makes it in
    every layer lowers the kernel once).  Grid (expert e, column tile
    f), the offsets scalar-prefetched: a step multiplies the windows of
    the slab that cover the expert's range with its tile of the front
    matrices and of `down` and adds the rows of the range to the float32
    result; slab and result lie in VMEM once, copied in at the first step and
    out at the last.  An expert with no row stays on the tile read last
    (the first one to come, before any is), so the pipeline copies
    nothing for it."""
    C, D = xs.shape
    E, F, _ = down.shape
    item, matrices = down.dtype.itemsize, len(front) + 1
    tf = grouped_tile(C, D, F, item, matrices)
    nf = F // tf
    rows = _whole_tiles(C)
    if rows != C:
        xs = jnp.pad(xs, ((0, rows - C), (0, 0)))
    offsets = offsets.astype(jnp.int32)
    at = jnp.arange(E, dtype=jnp.int32)
    full = offsets[1:] > offsets[:-1]
    before = jax.lax.cummax(jnp.where(full, at, -1))    # the last read
    ahead = jnp.min(jnp.where(full, at, E - 1))         # the first to come
    src = jnp.where(before >= 0, before, ahead)
    col = jnp.where(before >= 0, nf - 1, 0)

    def tile(e, f, off, src, col):   # (expert, column tile) of a grid step
        own = off[e + 1] > off[e]
        return jnp.where(own, e, src[e]), jnp.where(own, f, col[e])

    def cols_of(*step):
        expert, t = tile(*step)
        return expert, 0, t

    rows_of = pl.BlockSpec((None, tf, D), lambda *step: (*tile(*step), 0))
    turned = _turned(F)
    if turned:
        front = {k: jnp.swapaxes(w_, 1, 2) for k, w_ in front.items()}
    cols = rows_of if turned else pl.BlockSpec((None, D, tf), cols_of)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(E, nf),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            *[cols] * len(front),
            rows_of,
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((rows + GROUPED_WINDOW, D), xs.dtype),
            pltpu.VMEM((rows + GROUPED_WINDOW, D), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, rows=rows, turned=turned),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
            vmem_limit_bytes=grouped_vmem(C, D, tf, item, matrices)),
        name="grouped_experts",
        interpret=interpret,
    )(offsets, src, col, xs, *front.values(), down)
    return out[:C]
