"""Bucketed gradient-reduction wire for the dense data-parallel path.

Round-5 measured the dense DP step at 270 ms on the 2-process TCP fabric
vs 53 ms for the onebit `sign` wire carrying the SAME bytes: the gap is
~40 per-leaf collectives (XLA's implicit psum at the loss-mean boundary)
vs one fused buffer, and per-collective latency dominates on
serialization-bound fabrics.  This module is the reference's bucketing
recipe (stage2.py:614-745 flatten/reduce machinery, ZeRO §5 of
1910.02054) rebuilt as a STATIC plan the jitted step consumes:

* `BucketPlan` is computed ONCE at `initialize()` from the gradient tree
  — dtype-segregated, size-capped flat buckets (honoring the config's
  `reduce_bucket_size`, in elements like the reference) with precomputed
  per-leaf offsets.  No per-step Python walks the tree to decide layout.
* Inside the jitted step (under `shard_map` over the `data` axis) the
  local gradients concatenate into the plan's buckets and ride ONE
  collective per bucket instead of one per leaf.
* Wire modes select what crosses the fabric:
    - "fp32"  psum of the fp32 bucket (the `fp32_allreduce` /
              `allreduce_always_fp32` behaviour; default).
    - "bf16"  bucket cast to bf16 before the psum — half the bytes,
              ~8-bit mantissa accumulation (XLA sums bf16 natively).
    - "split" the EleutherAI 24-bit frexp wire (compressed_ar.py) riding
              GATHER semantics: each rank's bucket decomposes into an
              fp16 mantissa + int8 exponent (3 bytes/elem), both
              all-gathered, then ldexp-reconstructed in fp32 and summed
              locally.  Per-contribution relative error is ≤ 2^-11
              (fp16 mantissa) — tighter than bf16's 2^-8 — and, unlike
              an arithmetic reduce (which XLA upcasts BEFORE the
              transfer), gather
              semantics keep the narrow dtype ON the wire.
* For ZeRO stage >= 2 the bucket reduction lowers to `psum_scatter`
  (reduce-scatter): each dp rank materializes only the bucket shards its
  optimizer partition owns; the post-step parameter all-gather rides
  XLA's sharding propagation exactly as before (zero/partition.py).
* With a HIERARCHICAL data axis (comm/mesh.py `data_outer`/`data_inner`
  sub-axes; the ZeRO++ two-level recipe, arXiv:2306.10209) each bucket
  lowers per level: `psum_scatter` over `data_inner` (fast fabric, full
  bucket) -> inter-group collective over `data_outer` on the 1/inner
  shard only (slow fabric — each level selects its own wire mode, so
  this hop can ride bf16, the 24-bit split gather, or the blockwise
  int8/int4 quantized gather while the fast hop stays exact) ->
  `all_gather` over `data_inner` back to the full bucket.  Slow-fabric
  bytes drop by the inner-group factor vs the flat wire.  Under
  ZeRO >= 2 the final gather is skipped entirely: buckets leave sharded
  over `data_inner`, which is exactly where the hpZ-style secondary
  optimizer partitions live (zero/partition.py places shards on
  `data_inner` only), so the post-step parameter all-gather is
  intra-group and the inter-group cost is just the scatter already
  paid.
* The "int8" / "int4" wires are qgZ's compression half (comm/quant.py):
  each rank blockwise-quantizes its contribution ONCE (per-block fp16
  scales ride the wire alongside the payload), the narrow bytes
  all-gather, and every rank dequantizes to fp32 and sums locally — the
  reduction always happens in the wide accumulator, so quantization
  error never compounds across ranks.  Like "split" they are
  gather-structured (a psum cannot carry scales), so they cannot run
  the intra-group scatter level; placed on the OUTER hop they are
  priced per outer group, exactly where the Frontier-class
  low-bandwidth-partitioning recipe wants the hardest compression.

Every traced collective records its payload into the monitor COUNTERS
(`bucket.*`, traced-occurrence semantics like `dist.*`); the engine adds
per-dispatch `grad_wire.reduce` counts from `wire_bytes_per_reduction` /
`collectives_per_reduction` so byte accounting is auditable per step
(tests/test_grad_bucketing.py pins the two against each other).
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...comm.mesh import DATA_AXIS
from .quant import (DEFAULT_BLOCK_SIZE, QUANT_WIRES, payload_bytes,
                    validate_block_size)

WIRE_MODES = ("fp32", "bf16", "split", "int8", "int4")

# wires that ride all-gather semantics (narrow dtypes + sideband data
# stay ON the wire; an arithmetic reduce would upcast before the
# transfer and, for the quantized wires, has no way to carry scales)
GATHER_WIRES = ("split",) + QUANT_WIRES

# bytes per element actually handed to the collective, per fixed-width
# wire mode (the quantized wires price via quant.payload_bytes — their
# per-element cost depends on the block size)
_WIRE_ITEMSIZE = {"fp32": 4, "bf16": 2, "split": 3}  # fp16 m + int8 e


def wire_nbytes(n_elems: int, wire: str, block: int, *,
                padded: bool = True) -> int:
    """Exact per-rank wire bytes for `n_elems` elements in `wire` mode.
    `padded=False` prices the logical payload (no block-padding
    overhead) for the `*_logical` counters; fixed-width wires have no
    block padding, so both views agree there."""
    if wire in QUANT_WIRES:
        return payload_bytes(n_elems, wire, block, padded=padded)
    return n_elems * _WIRE_ITEMSIZE[wire]


def _record(op: str, nbytes: int) -> None:
    """Traced-occurrence counter (once per compiled program, like the
    `dist.*` wrappers) — never raises into a trace."""
    try:
        from ...monitor.counters import COUNTERS

        COUNTERS.add(f"bucket.{op}", nbytes)
    except Exception:
        pass


class WireLevel(NamedTuple):
    """One level of a hierarchical reduction: the mesh axis it rides,
    the group size, and the wire mode its payload crosses the fabric
    in."""

    axis: str             # mesh axis name ("data_inner" / "data_outer")
    size: int             # group size along that axis
    wire: str             # "fp32" | "bf16" | "split" (outer level only)


class LeafSlot(NamedTuple):
    """Where one gradient leaf lives inside its bucket."""

    leaf_id: int          # index in tree_flatten order
    offset: int           # element offset into the flat bucket
    size: int             # element count
    shape: Tuple[int, ...]


class BucketSpec(NamedTuple):
    dtype: Any            # numpy dtype of the leaves in this bucket
    slots: Tuple[LeafSlot, ...]
    n_elems: int          # payload elements (sum of slot sizes)
    padded: int           # n_elems rounded up for reduce-scatter


class BucketPlan:
    """Static flat-bucket layout + the in-jit reduce that consumes it.

    Built once from the gradient tree STRUCTURE (shapes/dtypes — arrays
    or ShapeDtypeStructs both work); all methods taking gradient values
    are pure and trace-safe.
    """

    def __init__(self, grad_tree, *, dp_size: int, axis: str = DATA_AXIS,
                 bucket_elems: int, wire: str = "fp32",
                 scatter: bool = False,
                 levels: Optional[Tuple[WireLevel, WireLevel]] = None,
                 quant_block: int = DEFAULT_BLOCK_SIZE):
        if wire not in WIRE_MODES:
            raise ValueError(
                f"unknown wire mode {wire!r}; choose from {WIRE_MODES}")
        if bucket_elems <= 0:
            raise ValueError(f"reduce_bucket_size must be > 0, "
                             f"got {bucket_elems}")
        if levels is not None:
            inner, outer = levels[0], levels[1]
            for name, lvl in (("inner", inner), ("outer", outer)):
                if lvl.wire not in WIRE_MODES:
                    raise ValueError(
                        f"unknown {name}-level wire mode {lvl.wire!r}; "
                        f"choose from {WIRE_MODES}")
            if inner.size * outer.size != int(dp_size):
                raise ValueError(
                    f"hierarchy levels {outer.size} x {inner.size} do not "
                    f"factor the data-parallel size {dp_size}")
            if inner.size <= 1 or outer.size <= 1:
                raise ValueError(
                    f"hierarchy levels must both be > 1 (got outer="
                    f"{outer.size}, inner={inner.size}); use a flat plan "
                    "for a single-level reduction")
            if inner.wire in GATHER_WIRES:
                # gather-structured: an intra-level gather wire would
                # re-materialize the full bucket on every rank and hand
                # the OUTER hop full-width payloads — the hierarchy's
                # whole point inverted (and a psum_scatter has no way to
                # carry the quantized wires' per-block scales).  Config
                # sanitizes an inherited request to fp32; direct
                # constructions must not slip through.
                raise ValueError(
                    f"the {inner.wire} wire is gather-structured and "
                    "cannot run the intra-group scatter level; use fp32 "
                    "or bf16 for the inner wire")
            self.levels: Optional[Tuple[WireLevel, WireLevel]] = \
                (inner, outer)
        else:
            self.levels = None
        if scatter and wire in GATHER_WIRES and levels is None:
            # gather wires re-materialize the full bucket on every rank
            # anyway, so a scattered lowering buys nothing.  Callers
            # (engine._build_bucket_plan) log the fallback.
            scatter = False
        self.axis = axis
        self.dp_size = int(dp_size)
        self.wire = wire
        self.scatter = bool(scatter)
        self.bucket_elems = int(bucket_elems)
        self.quant_block = validate_block_size(quant_block)

        leaves, self.treedef = jax.tree_util.tree_flatten(grad_tree)
        self._leaf_shapes = [tuple(l.shape) for l in leaves]
        self._leaf_dtypes = [np.dtype(l.dtype) for l in leaves]

        self.buckets: List[BucketSpec] = []
        open_by_dtype = {}  # dtype -> (slots, fill)
        for lid, leaf in enumerate(leaves):
            shape = tuple(leaf.shape)
            size = int(np.prod(shape or (1,), dtype=np.int64))
            dt = np.dtype(leaf.dtype)
            slots, fill = open_by_dtype.get(dt, ([], 0))
            if slots and fill + size > self.bucket_elems:
                self._close(dt, slots, fill)
                slots, fill = [], 0
            slots.append(LeafSlot(lid, fill, size, shape))
            fill += size
            open_by_dtype[dt] = (slots, fill)
            if fill >= self.bucket_elems:
                self._close(dt, slots, fill)
                open_by_dtype[dt] = ([], 0)
        for dt, (slots, fill) in open_by_dtype.items():
            if slots:
                self._close(dt, slots, fill)

        # wire accounting, fixed at plan-build time.  For hierarchical
        # plans the intra/inter split is the headline number: inter
        # (slow-fabric) bytes are the 1/inner-size shard per bucket.
        # Each figure also gets a *_logical twin pricing the same wire
        # with zero padding overhead — bucket padding to inner/block
        # multiples otherwise inflates the byte counters and masks part
        # of a compression win in BENCH comparisons.
        blk = self.quant_block
        if self.levels is not None:
            inner, outer = self.levels
            # dense: scatter + gather legs on the fast fabric; ZeRO>=2
            # keeps buckets scattered — the gather leg never runs
            intra_legs = 1 if self.scatter else 2
            self.wire_bytes_intra_per_reduction = sum(
                wire_nbytes(b.padded, inner.wire, blk) * intra_legs
                for b in self.buckets)
            self.wire_bytes_intra_logical_per_reduction = sum(
                wire_nbytes(b.n_elems, inner.wire, blk, padded=False)
                * intra_legs for b in self.buckets)
            self.collectives_intra_per_reduction = (
                intra_legs * len(self.buckets))
            self.wire_bytes_inter_per_reduction = sum(
                wire_nbytes(b.padded // inner.size, outer.wire, blk)
                for b in self.buckets)
            self.wire_bytes_inter_logical_per_reduction = sum(
                wire_nbytes(-(-b.n_elems // inner.size), outer.wire, blk,
                            padded=False) for b in self.buckets)
            # split ships mantissa + exponent as TWO gathers; the
            # quantized wires fuse payload + scales into ONE buffer
            self.collectives_inter_per_reduction = (
                (2 if outer.wire == "split" else 1) * len(self.buckets))
            self.wire_bytes_per_reduction = (
                self.wire_bytes_intra_per_reduction
                + self.wire_bytes_inter_per_reduction)
            self.wire_bytes_logical_per_reduction = (
                self.wire_bytes_intra_logical_per_reduction
                + self.wire_bytes_inter_logical_per_reduction)
            self.collectives_per_reduction = (
                self.collectives_intra_per_reduction
                + self.collectives_inter_per_reduction)
        else:
            self.wire_bytes_per_reduction = sum(
                wire_nbytes(b.padded, self.wire, blk)
                for b in self.buckets)
            self.wire_bytes_logical_per_reduction = sum(
                wire_nbytes(b.n_elems, self.wire, blk, padded=False)
                for b in self.buckets)
            self.collectives_per_reduction = (
                (2 if self.wire == "split" else 1) * len(self.buckets))
            self.wire_bytes_intra_per_reduction = 0
            self.wire_bytes_inter_per_reduction = 0
            self.wire_bytes_intra_logical_per_reduction = 0
            self.wire_bytes_inter_logical_per_reduction = 0
            self.collectives_intra_per_reduction = 0
            self.collectives_inter_per_reduction = 0

    def _close(self, dtype, slots, fill):
        # scatter shards over the (inner) axis; hierarchical plans also
        # psum_scatter dense buckets over the inner group — both need
        # the bucket length to divide evenly
        chunks = 1
        if self.levels is not None:
            chunks = self.levels[0].size
        elif self.scatter:
            chunks = self.dp_size
        pad = -fill % chunks if chunks > 1 else 0
        self.buckets.append(BucketSpec(dtype, tuple(slots), fill,
                                       fill + pad))

    # -- in-jit layout ops --------------------------------------------

    def flatten(self, grads) -> List[jnp.ndarray]:
        """Gradient tree -> list of flat buckets (zero-padded for the
        reduce-scatter lowering)."""
        leaves = jax.tree_util.tree_leaves(grads)
        out = []
        for b in self.buckets:
            parts = [leaves[s.leaf_id].reshape(-1) for s in b.slots]
            if b.padded > b.n_elems:
                parts.append(jnp.zeros((b.padded - b.n_elems,), b.dtype))
            out.append(jnp.concatenate(parts)
                       if len(parts) > 1 else parts[0])
        return out

    def unflatten(self, buckets) -> Any:
        """List of flat (reduced) buckets -> gradient tree."""
        leaves: List[Optional[jnp.ndarray]] = [None] * len(self._leaf_shapes)
        for b, flat in zip(self.buckets, buckets):
            for s in b.slots:
                leaves[s.leaf_id] = lax.slice(
                    flat, (s.offset,), (s.offset + s.size,)).reshape(s.shape)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- in-jit reduction (call inside shard_map over self.axis) ------

    def reduce(self, buckets) -> List[jnp.ndarray]:
        """Mean-reduce each flat bucket over the data axis: ONE collective
        per bucket (two for the split wire).  Must run in a manual-mesh
        region (shard_map) with `self.axis` (or, hierarchical, both level
        axes) bound."""
        if self.levels is not None:
            return [self._reduce_one_hier(flat, b) for flat, b in
                    zip(buckets, self.buckets)]
        return [self._reduce_one(flat, b) for flat, b in
                zip(buckets, self.buckets)]

    @staticmethod
    def _split_gather_sum(x, n_elems: int, axis: str, prefix: str):
        """The 24-bit frexp wire, shared by the flat split mode and the
        hierarchical outer hop: fp16 mantissa + int8 exponent of `x`
        all-gather over `axis`, ldexp-reconstruct and sum locally in
        fp32.  Gather semantics keep the narrow dtypes ON the wire — an
        arithmetic reduce upcasts before the transfer."""
        from .compressed_ar import decompose_int8_safe

        mantissa, exponent = decompose_int8_safe(x)
        _record(f"{prefix}all_gather", n_elems * 2)
        m_all = lax.all_gather(mantissa, axis, axis=0, tiled=False)
        _record(f"{prefix}all_gather", n_elems * 1)
        e_all = lax.all_gather(exponent.astype(jnp.int8), axis,
                               axis=0, tiled=False)
        return jnp.sum(jnp.ldexp(m_all.astype(jnp.float32),
                                 e_all.astype(jnp.int32)), axis=0)

    def _quant_gather_sum(self, x, wire: str, axis: str, prefix: str):
        """The blockwise-quantized gather wire (qgZ compression half,
        comm/quant.py): int8/int4 payload + per-block fp16 scales fused
        into ONE uint8 buffer (pack_wire) and all-gathered over `axis`;
        every rank dequantizes each peer's contribution to fp32 and
        sums LOCALLY — accumulate always in the wide domain, quantize
        only for the wire, so the error never compounds across ranks.
        One buffer matters: on latency-bound fabrics a separate scales
        collective would cost a second round-trip and hand the latency
        win right back."""
        from .quant import quantized_all_gather

        per_rank = quantized_all_gather(
            x, (axis,), self.quant_block, wire,
            record=lambda nb: _record(f"{prefix}all_gather", nb))
        return jnp.sum(per_rank, axis=0)

    def _reduce_one_hier(self, flat, spec: BucketSpec):
        """Two-level lowering: intra-group reduce-scatter (full bucket,
        fast fabric) -> inter-group collective on the 1/inner shard
        (slow fabric, its own wire mode) -> intra-group all-gather
        (skipped under ZeRO>=2: the bucket leaves sharded over the inner
        axis, where the hpZ optimizer partitions live)."""
        inner, outer = self.levels
        isz_in = _WIRE_ITEMSIZE[inner.wire]
        shard_elems = spec.padded // inner.size

        wired = flat.astype(jnp.bfloat16 if inner.wire == "bf16"
                            else jnp.float32)
        _record("intra.psum_scatter", spec.padded * isz_in)
        shard = lax.psum_scatter(wired, inner.axis, scatter_dimension=0,
                                 tiled=True).astype(jnp.float32)

        if outer.wire == "split":
            # the 24-bit frexp gather on the SLOW hop only — priced per
            # outer group, not per rank
            shard = self._split_gather_sum(shard, shard_elems,
                                           outer.axis, "inter.")
        elif outer.wire in QUANT_WIRES:
            # blockwise int8/int4 + fp16 scales on the slow hop only:
            # the qgZ placement — compression hardest on the slowest
            # fabric, fp32 accumulation everywhere
            shard = self._quant_gather_sum(shard, outer.wire, outer.axis,
                                           "inter.")
        elif outer.wire == "bf16":
            _record("inter.psum", shard_elems * 2)
            shard = lax.psum(shard.astype(jnp.bfloat16),
                             outer.axis).astype(jnp.float32)
        else:
            _record("inter.psum", shard_elems * 4)
            shard = lax.psum(shard, outer.axis)
        shard = shard / self.dp_size

        if self.scatter:
            return shard.astype(flat.dtype)
        gathered = shard.astype(jnp.bfloat16) if inner.wire == "bf16" \
            else shard
        _record("intra.all_gather", spec.padded * isz_in)
        out = lax.all_gather(gathered, inner.axis, axis=0, tiled=True)
        return out.astype(flat.dtype)

    def _reduce_one(self, flat, spec: BucketSpec):
        axis, dp = self.axis, self.dp_size
        nbytes = wire_nbytes(spec.padded, self.wire, self.quant_block)
        if self.wire == "bf16":
            wired = flat.astype(jnp.bfloat16)
            if self.scatter:
                _record("psum_scatter", nbytes)
                red = lax.psum_scatter(wired, axis, scatter_dimension=0,
                                       tiled=True)
            else:
                _record("psum", nbytes)
                red = lax.psum(wired, axis)
            return red.astype(flat.dtype) / dp
        if self.wire == "split":
            # 24-bit gather wire (compressed_ar.decompose_int8_safe —
            # subnormals flushed, the >= 2^127 tail pushed to inf so
            # overflow checks fire; the int8 exponent never wraps)
            total = self._split_gather_sum(flat, spec.padded, axis, "")
            return (total / dp).astype(flat.dtype)
        if self.wire in QUANT_WIRES:
            # blockwise-quantized gather wire (comm/quant.py: subnormal
            # flush + non-finite marker codes so overflow checks fire)
            total = self._quant_gather_sum(flat, self.wire, axis, "")
            return (total / dp).astype(flat.dtype)
        # fp32-accumulate (allreduce_always_fp32 semantics)
        wired = flat.astype(jnp.float32)
        if self.scatter:
            _record("psum_scatter", nbytes)
            red = lax.psum_scatter(wired, axis, scatter_dimension=0,
                                   tiled=True)
        else:
            _record("psum", nbytes)
            red = lax.psum(wired, axis)
        return (red / dp).astype(flat.dtype)

    # -- overlap lowering (runtime/comm/overlap.py host exchange) -----
    #
    # The overlapped wire splits each bucket's reduction in two at the
    # point where bytes would cross the slow fabric: `overlap_encode`
    # runs in the GRADS program (after the hierarchical plan's
    # intra-group psum_scatter — the fast-fabric leg stays an XLA
    # collective) and emits this rank's wire payload as one flat uint8
    # buffer; the host exchange moves every rank's buffer while the
    # device runs the next micro-step's program; `overlap_combine` runs
    # in the COMBINE program over the gathered [world, nbytes] matrix
    # and reduces with EXPRESSIONS BIT-IDENTICAL to the serial path's:
    # an explicit rank-ordered linear fold where the serial wire rides
    # psum/psum_scatter (XLA:CPU lowers both to exactly that ordered
    # sum — pinned by tests), and the gather wires' own jnp.sum
    # accumulation where the serial wire is gather-structured.  Losses
    # and params under overlap are bitwise those of the serial wire.

    def _encode_elems(self, spec: BucketSpec) -> int:
        """Elements one rank contributes to the exchange for `spec`:
        the full padded bucket on a flat plan, the 1/inner-size shard
        after the intra-group scatter on a hierarchical one."""
        if self.levels is not None:
            return spec.padded // self.levels[0].size
        return spec.padded

    def _overlap_wire(self) -> str:
        """The wire mode whose payload crosses the host exchange: the
        outer level's on hierarchical plans, the single wire flat."""
        return self.levels[1].wire if self.levels is not None else self.wire

    @property
    def overlap_layout(self):
        """[(offset, nbytes, elems)] of each bucket inside the fused
        per-rank exchange buffer + the buffer's total size."""
        wire = self._overlap_wire()
        layout, off = [], 0
        for b in self.buckets:
            elems = self._encode_elems(b)
            nb = wire_nbytes(elems, wire, self.quant_block)
            layout.append((off, nb, elems))
            off += nb
        return layout, off

    def _encode_one(self, x, wire: str):
        """fp32 values -> this rank's uint8 wire bytes for one bucket
        (sized exactly `wire_nbytes(x.size, wire, quant_block)`)."""
        if wire == "fp32":
            return lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.uint8).reshape(-1)
        if wire == "bf16":
            return lax.bitcast_convert_type(
                x.astype(jnp.bfloat16), jnp.uint8).reshape(-1)
        if wire == "split":
            from .compressed_ar import decompose_int8_safe

            m, e = decompose_int8_safe(x)
            return jnp.concatenate([
                lax.bitcast_convert_type(m, jnp.uint8).reshape(-1),
                lax.bitcast_convert_type(e.astype(jnp.int8),
                                         jnp.uint8).reshape(-1)])
        from .quant import pack_wire, quantize_blockwise

        payload, scales = quantize_blockwise(x, self.quant_block, wire)
        return pack_wire(payload, scales)

    def overlap_encode(self, buckets) -> jnp.ndarray:
        """Flat local-grad buckets -> ONE fused uint8 exchange buffer
        for this rank.  Must run inside the grads program's shard_map
        region: hierarchical plans run the intra-group psum_scatter
        here (the fast-fabric leg — identical op to the serial path's),
        so only the 1/inner shard rides the host exchange."""
        wire = self._overlap_wire()
        parts = []
        for flat, spec in zip(buckets, self.buckets):
            x = flat
            if self.levels is not None:
                inner = self.levels[0]
                isz_in = _WIRE_ITEMSIZE[inner.wire]
                wired = flat.astype(jnp.bfloat16 if inner.wire == "bf16"
                                    else jnp.float32)
                _record("intra.psum_scatter", spec.padded * isz_in)
                x = lax.psum_scatter(wired, inner.axis,
                                     scatter_dimension=0,
                                     tiled=True).astype(jnp.float32)
            parts.append(self._encode_one(x, wire))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def overlap_encode_out_spec(self):
        """Out spec stacking each rank's exchange buffer rank-major:
        (outer, inner) on hierarchical meshes, the data axis flat."""
        if self.levels is not None:
            return P((self.levels[1].axis, self.levels[0].axis))
        return P(self.axis)

    @staticmethod
    def _decode_rows(rows, wire: str, elems: int, block: int):
        """[world, nbytes] uint8 -> per-rank fp32/narrow values, shaped
        [world, elems] (bf16 rows stay bf16 so the fold accumulates at
        the same width the serial psum did)."""
        if wire == "fp32":
            return lax.bitcast_convert_type(
                rows.reshape(rows.shape[0], elems, 4), jnp.float32)
        if wire == "bf16":
            return lax.bitcast_convert_type(
                rows.reshape(rows.shape[0], elems, 2), jnp.bfloat16)
        raise ValueError(wire)  # split/quant decode inline in combine

    @staticmethod
    def _fold(vals):
        """Rank-ordered linear sum over the leading world dim — the
        association XLA:CPU's psum/psum_scatter lowers to (pinned by
        tests/test_step_overlap.py), NOT jnp.sum's pairwise tree."""
        acc = vals[0]
        for r in range(1, vals.shape[0]):
            acc = acc + vals[r]
        return acc

    def _combine_one(self, rows, spec: BucketSpec, dtype):
        """One bucket's gathered [world, nbytes] rows -> the reduced
        bucket (or this rank's shard under a scattered lowering),
        mirroring `_reduce_one` / `_reduce_one_hier` expression for
        expression."""
        elems = self._encode_elems(spec)
        wire = self._overlap_wire()
        blk = self.quant_block

        if self.levels is not None:
            inner, outer = self.levels
            # this rank consumes its outer peers' shards at its own
            # inner index (rank-major rows: rank = o * inner + i)
            i = lax.axis_index(inner.axis)
            rows = jnp.take(rows, jnp.arange(outer.size) * inner.size + i,
                            axis=0)

        if wire == "split":
            m = lax.bitcast_convert_type(
                rows[:, :elems * 2].reshape(rows.shape[0], elems, 2),
                jnp.float16)
            e = lax.bitcast_convert_type(
                rows[:, elems * 2:].reshape(rows.shape[0], elems, 1),
                jnp.int8).reshape(rows.shape[0], elems)
            total = jnp.sum(jnp.ldexp(m.astype(jnp.float32),
                                      e.astype(jnp.int32)), axis=0)
        elif wire in QUANT_WIRES:
            from .quant import unpack_wire, dequantize_blockwise

            p, s = unpack_wire(rows, wire, blk, elems)
            total = jnp.sum(dequantize_blockwise(p, s, wire, elems),
                            axis=0)
        else:
            vals = self._decode_rows(rows, wire, elems, blk)
            if wire == "bf16":
                # XLA's bf16 psum/psum_scatter accumulate at f32 width
                # and round the RESULT to bf16 (pinned by
                # tests/test_step_overlap.py) — mirror exactly
                vals = vals.astype(jnp.float32)
            if self.scatter and self.levels is None:
                chunk = spec.padded // self.dp_size
                r = lax.axis_index(self.axis)
                vals = lax.dynamic_slice_in_dim(vals, r * chunk, chunk,
                                                axis=1)
            total = self._fold(vals)
            if wire == "bf16":
                total = total.astype(jnp.bfloat16)
            if self.levels is None:
                # flat psum parity: bf16 casts the (rounded) result up
                # then divides (serial: psum(bf16).astype(f32)/dp);
                # fp32 divides first then casts
                if wire == "bf16":
                    return total.astype(dtype) / self.dp_size
                return (total.astype(jnp.float32) / self.dp_size
                        ).astype(dtype)

        if self.levels is None:
            return (total / self.dp_size).astype(dtype)

        # hierarchical tail: mirror _reduce_one_hier after the outer hop
        inner, outer = self.levels
        shard = total.astype(jnp.float32) / self.dp_size
        if self.scatter:
            return shard.astype(dtype)
        gathered = shard.astype(jnp.bfloat16) if inner.wire == "bf16" \
            else shard
        isz_in = _WIRE_ITEMSIZE[inner.wire]
        _record("intra.all_gather", spec.padded * isz_in)
        out = lax.all_gather(gathered, inner.axis, axis=0, tiled=True)
        return out.astype(dtype)

    def overlap_combine(self, matrix) -> List[jnp.ndarray]:
        """Gathered [world, total_nbytes] exchange matrix -> reduced
        buckets.  Must run inside the combine program's shard_map
        region (same axis names as the grads program)."""
        layout, _total = self.overlap_layout
        out = []
        for (off, nb, _elems), spec in zip(layout, self.buckets):
            rows = lax.slice(matrix, (0, off),
                             (matrix.shape[0], off + nb))
            out.append(self._combine_one(rows, spec, jnp.float32))
        return out

    # -- shard_map plumbing -------------------------------------------

    def bucket_out_specs(self):
        """Out specs for the reduced buckets: scattered buckets leave the
        manual region sharded over the data axis (each rank holds only
        its shard — the ZeRO-2 wire contract), full reductions leave
        replicated.  Hierarchical scattered buckets are sharded over the
        INNER axis only (replicated across outer groups): exactly the
        hpZ secondary-shard placement zero/partition.py gives the
        optimizer state, so the post-step gather stays intra-group."""
        if self.scatter:
            spec = P(self.levels[0].axis if self.levels is not None
                     else self.axis)
        else:
            spec = P()
        return [spec for _ in self.buckets]

    # -- introspection ------------------------------------------------

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def n_leaves(self) -> int:
        return len(self._leaf_shapes)

    @property
    def total_elems(self) -> int:
        return sum(b.n_elems for b in self.buckets)

    @property
    def hierarchical(self) -> bool:
        return self.levels is not None

    @property
    def exact_fp32(self) -> bool:
        """True when every hop accumulates at full fp32 width — the
        `allreduce_always_fp32` contract the engine reports."""
        if self.levels is not None:
            return all(lvl.wire == "fp32" for lvl in self.levels)
        return self.wire == "fp32"

    @property
    def quantized(self) -> bool:
        """True when any hop rides a blockwise-quantized wire."""
        if self.levels is not None:
            return any(lvl.wire in QUANT_WIRES for lvl in self.levels)
        return self.wire in QUANT_WIRES

    def describe(self) -> str:
        sizes = ", ".join(f"{b.n_elems}" + (f"+{b.padded - b.n_elems}pad"
                                            if b.padded > b.n_elems else "")
                          for b in self.buckets)
        lowering = "reduce-scatter" if self.scatter else "allreduce"
        if self.quantized:
            lowering += f", quant block={self.quant_block}"
        if self.levels is not None:
            inner, outer = self.levels
            return (f"BucketPlan: {self.n_leaves} grad leaves -> "
                    f"{self.n_buckets} bucket(s) [{sizes}] elems, "
                    f"hierarchical ({lowering}): intra {inner.axis}="
                    f"{inner.size} wire={inner.wire} "
                    f"({self.wire_bytes_intra_per_reduction} B / "
                    f"{self.collectives_intra_per_reduction} coll), "
                    f"inter {outer.axis}={outer.size} wire={outer.wire} "
                    f"({self.wire_bytes_inter_per_reduction} B / "
                    f"{self.collectives_inter_per_reduction} coll) "
                    f"per reduction over dp={self.dp_size}")
        return (f"BucketPlan: {self.n_leaves} grad leaves -> "
                f"{self.n_buckets} bucket(s) [{sizes}] elems, "
                f"wire={self.wire} ({lowering}), "
                f"{self.wire_bytes_per_reduction} wire bytes / "
                f"{self.collectives_per_reduction} collective(s) per "
                f"reduction over dp={self.dp_size}")
