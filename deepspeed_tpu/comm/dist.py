"""`dist`-shaped facade over XLA collectives.

The reference talks to torch.distributed (NCCL) directly:
broadcast / all_reduce / reduce / reduce_scatter / all_gather /
all_to_all_single, plus p2p emulated by 2-rank broadcast groups
(/root/reference/deepspeed/runtime/pipe/p2p.py:31-75,
 deepspeed/utils/distributed.py:12-51).

Here the same call-sites map to `jax.lax` collectives over named mesh axes.
Two usage modes:

1. *In-jit* (inside `shard_map`/`pmap` with a bound axis name): the functions
   below are thin wrappers over lax.psum / all_gather / psum_scatter /
   ppermute / all_to_all. This is the hot path — XLA lowers these onto ICI.
2. *Host-level* (single-controller): `init_distributed`, `barrier`,
   `get_rank`/`get_world_size` — process bootstrap via
   `jax.distributed.initialize` instead of a MASTER_ADDR NCCL rendezvous.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..constants import TORCH_DISTRIBUTED_DEFAULT_PORT
from ..utils.logging import logger
from . import mesh as mesh_mod

_INITIALIZED = False


class ReduceOp:
    """torch.distributed.ReduceOp parity."""

    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


# ---------------------------------------------------------------------------
# Host-level bootstrap (reference: deepspeed.init_distributed,
# utils/distributed.py:12-51 incl. MPI discovery :54-96)
# ---------------------------------------------------------------------------

def init_distributed(
    dist_backend: str = "xla",
    auto_mpi_discovery: bool = True,
    distributed_port: int = TORCH_DISTRIBUTED_DEFAULT_PORT,
    verbose: bool = True,
    timeout=None,
    init_method: Optional[str] = None,
):
    """Initialize multi-process JAX if a coordinator is configured.

    Signature mirrors reference `deepspeed.init_distributed`; the backend
    string is accepted for compatibility but the transport is always XLA
    over ICI/DCN. Single-process (or already-initialized) calls are no-ops.

    Coordinator discovery order:
      1. explicit env: DSTPU_COORDINATOR / DSTPU_NUM_PROCESSES / DSTPU_PROCESS_ID
      2. torch-style env: MASTER_ADDR(+distributed_port) / WORLD_SIZE / RANK
      3. OMPI env (auto_mpi_discovery): OMPI_COMM_WORLD_SIZE/RANK
      4. TPU-pod metadata (jax.distributed.initialize() auto-detect)
    """
    global _INITIALIZED
    if _INITIALIZED:
        return

    coord = os.environ.get("DSTPU_COORDINATOR")
    nprocs = os.environ.get("DSTPU_NUM_PROCESSES")
    pid = os.environ.get("DSTPU_PROCESS_ID")

    if coord is None and os.environ.get("MASTER_ADDR"):
        coord = f"{os.environ['MASTER_ADDR']}:{os.environ.get('MASTER_PORT', distributed_port)}"
        nprocs = nprocs or os.environ.get("WORLD_SIZE")
        pid = pid or os.environ.get("RANK")

    if coord is None and auto_mpi_discovery and os.environ.get("OMPI_COMM_WORLD_SIZE"):
        nprocs = nprocs or os.environ.get("OMPI_COMM_WORLD_SIZE")
        pid = pid or os.environ.get("OMPI_COMM_WORLD_RANK")
        coord = os.environ.get("DSTPU_COORDINATOR", "127.0.0.1:%d" % distributed_port)

    try:
        if coord is not None and nprocs is not None and int(nprocs) > 1:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(nprocs),
                process_id=int(pid or 0),
            )
            if verbose:
                logger.info(
                    f"jax.distributed initialized: coordinator={coord} "
                    f"process {pid}/{nprocs}"
                )
    except RuntimeError as e:  # already initialized by launcher
        logger.debug(f"jax.distributed.initialize skipped: {e}")
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED or jax.process_count() >= 1


def get_world_size(group: Optional[str] = None) -> int:
    """Global device count, or the size of one mesh axis (`group` = axis name).

    Reference process groups become mesh-axis handles."""
    if group is None:
        return jax.device_count()
    return mesh_mod.get_current_mesh().axis_size(group)


def get_rank(group: Optional[str] = None) -> int:
    """Host-level: process index (reference torch.distributed.get_rank)."""
    if group is None:
        return jax.process_index()
    raise ValueError(
        "per-axis rank is only meaningful inside shard_map; use axis_index(axis)"
    )


def get_local_rank() -> int:
    return int(os.environ.get("DSTPU_LOCAL_RANK", 0))


def barrier():
    """Cross-process barrier (reference torch.distributed.barrier)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("deepspeed_tpu.barrier")


# ---------------------------------------------------------------------------
# In-jit collectives (must run under shard_map/pmap with bound axis names).
# These are the TPU-native equivalents of the reference's NCCL calls; XLA
# schedules them on ICI and overlaps with compute automatically — no
# hand-managed side streams (contrast zero/stage2.py:680-686).
# ---------------------------------------------------------------------------

def axis_index(axis: str):
    """This shard's coordinate along `axis` (reference: group rank)."""
    return lax.axis_index(axis)


def _record_volume(kind: str, x) -> None:
    """Collective-volume counter (monitor/counters.py).  These wrappers
    execute under jit/shard_map TRACING, so each record counts one traced
    occurrence per compiled program (the per-program collective volume),
    not one per device execution — hence the `dist.` prefix, distinct
    from the per-dispatch `p2p.*` counters.  Never raises into a trace."""
    try:
        from ..monitor.counters import COUNTERS, tree_bytes

        COUNTERS.add(f"dist.{kind}", tree_bytes(x))
    except Exception:
        pass


def all_reduce(x, axis: str, op: str = ReduceOp.SUM):
    _record_volume("all_reduce", x)
    if op == ReduceOp.SUM:
        return lax.psum(x, axis)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis)
    if op == ReduceOp.PROD:
        return jnp.exp(lax.psum(jnp.log(x), axis))
    raise ValueError(f"unknown reduce op {op}")


def all_gather(x, axis: str, *, tiled: bool = True, gather_axis: int = 0):
    """Gather shards along `axis`; tiled=True concatenates along gather_axis
    (torch all_gather + cat), False stacks a new leading dim."""
    _record_volume("all_gather", x)
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_axis: int = 0, tiled: bool = True):
    """Sum across `axis` then keep this shard's slice — the ZeRO gradient
    primitive (reference zero/stage1.py:629 reduce_scatter_gradients)."""
    _record_volume("reduce_scatter", x)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=tiled)


def broadcast(x, axis: str, src: int = 0):
    """Every shard gets shard `src`'s value (reference dist.broadcast)."""
    _record_volume("broadcast", x)
    gathered = lax.all_gather(x, axis, axis=0, tiled=False)
    return jax.tree_util.tree_map(lambda g: g[src], gathered)


def ppermute(x, axis: str, perm):
    """Point-to-point ring/pair exchange — replaces the reference's
    2-rank-broadcast-group p2p (pipe/p2p.py:31-75) with ICI collective
    permute."""
    _record_volume("ppermute", x)
    return lax.ppermute(x, axis, perm)


def send_recv_next(x, axis: str):
    """Shift +1 along a ring: stage i -> stage i+1 (pipeline activations)."""
    _record_volume("ppermute", x)
    n = lax.axis_size(axis)
    return lax.ppermute(x, axis, [(i, (i + 1) % n) for i in range(n)])


def send_recv_prev(x, axis: str):
    """Shift -1 along a ring (pipeline gradients)."""
    _record_volume("ppermute", x)
    n = lax.axis_size(axis)
    return lax.ppermute(x, axis, [(i, (i - 1) % n) for i in range(n)])


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int):
    """reference dist.all_to_all_single (comm/nccl.py:99) — Ulysses-style
    head<->sequence scatter rides this on ICI."""
    _record_volume("all_to_all", x)
    return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis,
                          tiled=True)
