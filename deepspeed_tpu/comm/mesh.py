"""Device-mesh construction and registry — the comm substrate.

This replaces the reference's process-group bootstrap
(/root/reference/deepspeed/utils/distributed.py:12-51) with a TPU-native
design: instead of NCCL process groups, every parallelism axis is a named
axis of one `jax.sharding.Mesh` laid out over ICI (within a pod slice) and
DCN (across slices). Process groups in the reference map to mesh axes here:

    data parallel group   -> axis "data"   (ZeRO shards over this axis too)
    model parallel group  -> axis "model"  (tensor parallelism; reference
                                            delegates this to Megatron's mpu,
                                            here it is first-class)
    pipe parallel group   -> axis "pipe"   (pipeline stages)
    sequence parallelism  -> axis "seq"    (ring attention / long context;
                                            absent in the reference v0.3.15,
                                            first-class here)
    expert parallelism    -> axis "expert" (MoE; flattened into "data" when
                                            unused)

Axis order is chosen for ICI locality: "model" is innermost (adjacent
devices — per-layer collectives ride single-hop ICI), then "seq", then
"data"; "pipe" is outermost (only nearest-neighbor p2p traffic).

Hierarchical data axis (ZeRO++ / hpZ-style two-level reduction): when a
pod slice spans DCN (or processes talk over TCP), the `data` axis can be
factored into `("data_outer", "data_inner")` sub-axes — ICI-adjacent
ranks inner, cross-slice/cross-process outer — so the gradient wire can
reduce-scatter on the fast fabric, run the slow-fabric collective on the
1/inner shard only, and gather back on the fast fabric
(runtime/comm/bucketing.py).  Every consumer that thinks in terms of
"the data axis" goes through `MeshInfo.data_spec` / `data_axes`, which
collapse to plain `"data"` on a flat mesh — `data_outer == 1` is
EXACTLY today's layout.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.logging import logger

# Canonical axis names, outermost-to-innermost in ICI terms.
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
EXPERT_AXIS = "expert"

# Hierarchical factorization of the data axis (flat meshes never carry
# these names; `MeshInfo.data_axes` is the portable way to address "the
# data axis" on either layout).
DATA_OUTER_AXIS = "data_outer"  # slow fabric: cross-slice / cross-process
DATA_INNER_AXIS = "data_inner"  # fast fabric: ICI-adjacent ranks

AXIS_ORDER = (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS)
HIER_AXIS_ORDER = (PIPE_AXIS, DATA_OUTER_AXIS, DATA_INNER_AXIS, SEQ_AXIS,
                   MODEL_AXIS)

_CURRENT_MESH: Optional["MeshInfo"] = None


@dataclass
class MeshInfo:
    """A constructed mesh plus axis metadata.

    Plays the role of the reference's `PipelineParallelGrid`
    (/root/reference/deepspeed/runtime/pipe/topology.py:257-466): exposes
    per-axis sizes/ranks without torch process groups.
    """

    mesh: Mesh
    axis_sizes: Dict[str, int] = field(default_factory=dict)
    # (outer, inner) factorization of the data axis; None on flat meshes.
    # axis_sizes always keeps the LOGICAL "data" size (the product), so
    # every existing axis_size(DATA_AXIS) caller is layout-agnostic.
    data_hierarchy: Optional[Tuple[int, int]] = None

    @property
    def size(self) -> int:
        return int(np.prod([max(1, s) for s in self.axis_sizes.values()]))

    def axis_size(self, axis: str) -> int:
        if self.data_hierarchy is not None:
            if axis == DATA_OUTER_AXIS:
                return self.data_hierarchy[0]
            if axis == DATA_INNER_AXIS:
                return self.data_hierarchy[1]
        return self.axis_sizes.get(axis, 1)

    # -- hierarchical-data-axis surface -------------------------------

    @property
    def hierarchical(self) -> bool:
        return self.data_hierarchy is not None

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Mesh axis names the data dimension actually lives on,
        outermost first — `("data",)` flat, `("data_outer",
        "data_inner")` hierarchical.  Collectives over the whole dp
        group take this tuple (lax.psum/pmean accept it)."""
        if self.data_hierarchy is not None:
            return (DATA_OUTER_AXIS, DATA_INNER_AXIS)
        return (DATA_AXIS,)

    @property
    def data_spec(self):
        """The PartitionSpec entry for "sharded over the data axis":
        the plain axis name flat, the sub-axis tuple hierarchical."""
        return DATA_AXIS if self.data_hierarchy is None else \
            (DATA_OUTER_AXIS, DATA_INNER_AXIS)

    @property
    def data_outer_size(self) -> int:
        return self.data_hierarchy[0] if self.data_hierarchy else 1

    @property
    def data_inner_size(self) -> int:
        return (self.data_hierarchy[1] if self.data_hierarchy
                else self.axis_size(DATA_AXIS))

    def manual_axes(self, axes) -> frozenset:
        """`axis_names` for a `shard_map` whose body is manual over
        `axes`: those axes plus every other mesh axis of size 1.  A
        size-1 axis shards nothing, so the program is the same either
        way, and a region whose automatic axes all have size 1 lowers
        fully manual.  Partial-manual regions are where jax 0.9's
        `debug_callback` lowering and XLA:CPU's bf16 all-reduce
        promotion fail; TPU compiles both forms
        (tests/test_tpu_compile.py)."""
        return frozenset(axes) | {a for a, n in self.mesh.shape.items()
                                  if n == 1}

    def auto_axes(self) -> list:
        """Mesh axes of size > 1 that the code being traced is NOT
        manual over: what XLA would have to partition a call across
        (it cannot partition a Mosaic kernel)."""
        manual = jax.sharding.get_abstract_mesh().manual_axes
        return sorted(a for a, n in self.mesh.shape.items()
                      if n > 1 and a not in manual)

    # Reference-parity aliases (pipe/topology.py get_*_parallel_world_size)
    def get_data_parallel_world_size(self) -> int:
        return self.axis_size(DATA_AXIS)

    def get_model_parallel_world_size(self) -> int:
        return self.axis_size(MODEL_AXIS)

    def get_pipe_parallel_world_size(self) -> int:
        return self.axis_size(PIPE_AXIS)

    def get_seq_parallel_world_size(self) -> int:
        return self.axis_size(SEQ_AXIS)

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())


def _resolve_sizes(n_devices: int, sizes: Dict[str, int]) -> Dict[str, int]:
    """Resolve -1 ("take the rest") axis sizes against the device count."""
    resolved = {a: int(sizes.get(a, 1)) for a in AXIS_ORDER}
    free = [a for a, s in resolved.items() if s == -1]
    fixed = int(np.prod([s for s in resolved.values() if s != -1]))
    if n_devices % fixed != 0:
        raise ValueError(
            f"device count {n_devices} not divisible by fixed axis product {fixed} "
            f"(sizes={sizes})"
        )
    rest = n_devices // fixed
    if not free:
        if fixed != n_devices:
            raise ValueError(
                f"axis sizes {resolved} use {fixed} devices but {n_devices} are present"
            )
    elif len(free) == 1:
        resolved[free[0]] = rest
    else:
        raise ValueError("at most one axis size may be -1")
    return resolved


def derive_data_outer(dp_size: int) -> int:
    """Topology-derived outer factor for a hierarchical data axis: one
    outer group per jax process (the fast/slow fabric boundary — devices
    within a process share an address space / ICI, processes talk over
    DCN/TCP).  Returns 1 (flat) whenever a two-level wire cannot win:
    single process, dp not divisible by the process count, one device
    per process (inner groups of 1 reduce nothing on the fast fabric),
    or HETEROGENEOUS local device counts — make_mesh's contiguous
    reshape would then put a process boundary INSIDE an inner group,
    silently routing "fast-fabric" collectives over the slow link."""
    try:
        procs = jax.process_count()
    except Exception:
        procs = 1
    if procs <= 1 or dp_size % procs != 0 or dp_size // procs <= 1:
        return 1
    inner = dp_size // procs
    try:
        devs = jax.devices()
    except Exception:
        return 1
    if len(devs) == dp_size:
        # pure-DP (the only shape the hierarchy engages on): every
        # contiguous inner-sized run must sit inside ONE process
        for g in range(procs):
            owners = {getattr(d, "process_index", 0)
                      for d in devs[g * inner:(g + 1) * inner]}
            if len(owners) != 1:
                logger.warning(
                    f"comm.hierarchy auto: inner groups of {inner} do not "
                    f"align with process boundaries (processes contribute "
                    f"unequal local device counts) — keeping the flat "
                    f"data axis")
                return 1
    return procs


def elastic_device_slice(n_needed: int,
                         devices: Optional[Sequence] = None):
    """The device set for an elastic (shrunken-world) mesh: the first
    `n_needed` devices in `jax.devices()` order.

    In a true multi-process elastic restart the supervisor relaunched
    only the survivors, so the device count already matches and this is
    the identity.  When MORE devices are visible than the surviving
    world needs (a single-process virtual mesh simulating the shrink,
    or a host that kept its local devices while a peer died), the mesh
    is built over the leading contiguous slice — process-major order,
    so the surviving mesh keeps whole processes and the fast-fabric
    adjacency the hierarchy depends on."""
    devices = list(devices) if devices is not None else list(jax.devices())
    n_needed = int(n_needed)
    if n_needed < 1:
        raise ValueError(f"elastic world needs >= 1 device, got {n_needed}")
    if len(devices) < n_needed:
        raise ValueError(
            f"elastic world needs {n_needed} device(s) but only "
            f"{len(devices)} are visible — DSTPU_SURVIVING_WORLD cannot "
            f"exceed the relaunched job's capacity")
    if len(devices) > n_needed:
        logger.warning(
            f"elastic world: building the mesh over the first "
            f"{n_needed} of {len(devices)} visible devices "
            f"(surviving-world slice)")
    return devices[:n_needed]


def make_mesh(
    data: int = -1,
    model: int = 1,
    pipe: int = 1,
    seq: int = 1,
    data_outer: int = 1,
    devices: Optional[Sequence] = None,
    set_current: bool = True,
) -> MeshInfo:
    """Build a Mesh over the given axis sizes. -1 means "all remaining devices".

    Replaces reference `init_distributed` + mpu/topology plumbing
    (utils/distributed.py, pipe/topology.py) with one mesh.

    data_outer > 1 factors the data axis into ("data_outer",
    "data_inner") sub-axes for the hierarchical gradient wire: outer
    groups are contiguous runs of `jax.devices()` order (process-major,
    so with data_outer == process_count each process IS one inner
    group).  data_outer == 1 is exactly the flat layout.
    """
    devices = list(devices) if devices is not None else list(jax.devices())
    sizes = _resolve_sizes(len(devices), {
        DATA_AXIS: data, MODEL_AXIS: model, PIPE_AXIS: pipe, SEQ_AXIS: seq,
    })
    data_outer = int(data_outer)
    hierarchy = None
    if data_outer > 1:
        dp = sizes[DATA_AXIS]
        if dp % data_outer != 0:
            raise ValueError(
                f"data axis hierarchy: data_outer={data_outer} does not "
                f"divide the data-parallel size {dp} "
                f"(data_inner would be {dp / data_outer:g})")
        inner = dp // data_outer
        if inner == 1:
            # outer == dp: every "inner group" is one rank — nothing to
            # reduce on the fast fabric; flatten back to today's layout
            logger.debug(
                f"data hierarchy ({data_outer}, 1) is degenerate; "
                "using the flat data axis")
        else:
            hierarchy = (data_outer, inner)
    if hierarchy is not None:
        shape = (sizes[PIPE_AXIS], hierarchy[0], hierarchy[1],
                 sizes[SEQ_AXIS], sizes[MODEL_AXIS])
        # plain reshape, NOT mesh_utils: outer groups must stay
        # contiguous in jax.devices() order (process-major), which is
        # the fast/slow fabric boundary the hierarchy exists for —
        # a topology-optimizing permutation would scramble it
        dev_array = np.asarray(devices).reshape(shape)
        mesh = Mesh(dev_array, HIER_AXIS_ORDER)
        info = MeshInfo(mesh=mesh, axis_sizes=sizes,
                        data_hierarchy=hierarchy)
        if set_current:
            set_current_mesh(info)
        logger.debug(f"hierarchical mesh constructed: {sizes} with "
                     f"data=(outer {hierarchy[0]} x inner {hierarchy[1]}) "
                     f"over {len(devices)} devices")
        return info
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:  # heterogeneous/virtual platforms: plain reshape
        dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, AXIS_ORDER)
    info = MeshInfo(mesh=mesh, axis_sizes=sizes)
    if set_current:
        set_current_mesh(info)
    logger.debug(f"mesh constructed: {sizes} over {len(devices)} devices")
    return info


def set_current_mesh(info: MeshInfo) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = info


def peek_mesh() -> Optional["MeshInfo"]:
    """Current mesh or None — never constructs one (unlike
    get_current_mesh)."""
    return _CURRENT_MESH


def get_current_mesh() -> MeshInfo:
    global _CURRENT_MESH
    if _CURRENT_MESH is None:
        _CURRENT_MESH = make_mesh(set_current=False)
    return _CURRENT_MESH


@contextlib.contextmanager
def use_mesh(info: MeshInfo):
    global _CURRENT_MESH
    prev = _CURRENT_MESH
    _CURRENT_MESH = info
    try:
        with info.mesh:
            yield info
    finally:
        _CURRENT_MESH = prev


def largest_divisible_axis(shape: Sequence[int], size: int) -> Optional[int]:
    """Pick the best dimension to shard `size`-ways: the largest dim divisible
    by `size` (ties -> earliest). None if nothing divides."""
    best = None
    best_len = 0
    for i, d in enumerate(shape):
        if size > 0 and d % size == 0 and d > best_len:
            best, best_len = i, d
    return best
