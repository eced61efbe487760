"""Error-compensated 1-bit compressed collectives.

Reference: deepspeed/runtime/comm/nccl.py:47-186 (NcclBackend) and
mpi.py:34-290 (MpiBackend): sign-compress with worker error feedback,
all_to_all the sign bits + allgather the scales, server-side recompress
with server error feedback, allgather the result. CuPy packbits supplies
the bit-packing (runtime/compression/cupy.py).

TPU redesign: ICI is bandwidth-rich and XLA has no packed-int1 wire
format, so the same ALGORITHM (two-stage sign compression with both error
feedbacks — that is what 1-bit Adam's convergence proof needs) runs as a
pure function on mesh axes: signs travel through psum/pmean. The
`CompressedBackend` class mirrors the reference backend surface for
out-of-jit callers by shard_map-ping the pure function over the mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...comm.mesh import peek_mesh


def compressed_allreduce(x, worker_error, server_error, axis: Optional[str]):
    """1-bit compress with error feedback, average over `axis`, recompress.

    Returns (averaged_tensor, new_worker_error, new_server_error).
    Mirrors NcclBackend.compressed_allreduce (reference comm/nccl.py:47-186):
      worker: c = x + worker_error; scale = ||c||_1/n; send sign(c)*scale
      server: s = avg + server_error; rescale and sign again
    Call inside jit/shard_map with `axis` a mesh axis name, or axis=None
    for the single-shard (no-comm) case.
    """
    c = x + worker_error
    scale = jnp.mean(jnp.abs(c))
    compressed = jnp.sign(c) * scale
    new_worker_error = c - compressed

    if axis is not None:
        avg = lax.pmean(compressed, axis)
    else:
        avg = compressed

    s = avg + server_error
    server_scale = jnp.mean(jnp.abs(s))
    out = jnp.sign(s) * server_scale
    new_server_error = s - out
    return out, new_worker_error, new_server_error


INT8_GROUP = 2048  # elements per quantization scale (reference chunking)


def _quant_grouped(t, group=INT8_GROUP):
    """t: [..., k] with k % group == 0 -> (int8 same shape, fp32 scales
    [..., k/group]). Per-group scales keep small-magnitude regions
    (layernorm/bias momentum) from quantizing to zero under a layer with
    1000x larger values — the reference's per-chunk scale behavior
    (comm/nccl.py), at ~4 bytes per `group` wire bytes."""
    g = t.reshape(*t.shape[:-1], -1, group)
    scale = jnp.max(jnp.abs(g), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale[..., None]), -127, 127)
    return q.astype(jnp.int8).reshape(t.shape), scale


def _dequant_grouped(q, scale, group=INT8_GROUP):
    g = q.astype(jnp.float32).reshape(*q.shape[:-1], -1, group)
    return (g * scale[..., None]).reshape(q.shape)


def _group_for(n: int, W: int) -> int:
    """Quantization group sized to the tensor: full INT8_GROUP for large
    buffers, shrunk for small ones so a 16-element bias doesn't pad to
    W * 2048 (a ~1000x wire blowup for per-leaf callers)."""
    k0 = -(-n // W)  # ceil(n / W): per-worker chunk before rounding
    return max(1, min(INT8_GROUP, k0))


def int8_compressed_allreduce(x, worker_error, server_error, axis):
    """Error-compensated INT8 compressed mean over `axis` — the
    TPU-native compression SURVEY §2.3 recommends in place of bit-packing:
    XLA has no packed-int1 wire format (sign compression rides pmean at
    full width), but int8 collectives transmit
    int8, so this genuinely cuts wire bytes ~4x vs fp32.

    Same two-stage structure as the reference's 1-bit backends
    (comm/nccl.py:47-186) with both error feedbacks:
      worker: q = round((x + we) / scale_w) int8; all_to_all chunks
      server: owner sums its chunk, adds se, requantizes; allgather
    Wire per device: ~1 byte/elem a2a + ~1 byte/elem allgather + scales
    (dense fp32 ring allreduce moves ~8 bytes/elem).

    Call inside jit/shard_map with `axis` a mesh axis name (or None for
    the single-shard no-comm case). Returns (mean, new_we, new_se)."""
    if axis is None:
        n = x.size
        G = _group_for(n, 1)
        pad = (-n) % G
        c = jnp.pad((x + worker_error).ravel(), (0, pad))
        q, sw = _quant_grouped(c, G)
        deq = _dequant_grouped(q, sw, G)
        new_we = (c - deq)[:n].reshape(x.shape)
        s = deq + jnp.pad(server_error.ravel(), (0, pad))
        q2, ss = _quant_grouped(s, G)
        out = _dequant_grouped(q2, ss, G)
        return (out[:n].reshape(x.shape), new_we,
                (s - out)[:n].reshape(server_error.shape))

    W = lax.psum(1, axis)
    n = x.size
    G = _group_for(n, W)
    pad = (-n) % (W * G)  # rows must split into whole groups
    c = jnp.pad((x + worker_error).ravel(), (0, pad)).reshape(W, -1)
    q, sw = _quant_grouped(c, G)         # q [W, k] int8, sw [W, k/G]
    new_we = ((c - _dequant_grouped(q, sw, G)).ravel()[:n]
              .reshape(x.shape))
    # phase 1 (wire: int8 + fp32/2048 scales): worker j receives chunk
    # ROW j from everyone
    recv = lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                          tiled=False)                 # [W, k] int8
    rscale = lax.all_to_all(sw, axis, split_axis=0, concat_axis=0,
                            tiled=False)               # [W, k/G]
    avg = jnp.sum(_dequant_grouped(recv, rscale, G), axis=0) / W

    # server stage: per-owner error feedback on the owned chunk (the
    # state keeps the full-shape buffer for a static pytree; only the
    # owned row is meaningful on each worker, like the reference's
    # per-rank server_error slices)
    idx = lax.axis_index(axis)
    se_full = jnp.pad(server_error.ravel(), (0, pad)).reshape(W, -1)
    se_chunk = lax.dynamic_index_in_dim(se_full, idx, 0, keepdims=False)
    s = avg + se_chunk
    q2, ss = _quant_grouped(s, G)
    se_new_chunk = s - _dequant_grouped(q2, ss, G)
    new_se = jnp.zeros_like(se_full).at[idx].set(se_new_chunk)
    new_se = new_se.ravel()[:n].reshape(server_error.shape)

    # phase 2 (wire: int8 + fp32/2048 scales per owner)
    allq = lax.all_gather(q2, axis)    # [W, k] int8
    allsc = lax.all_gather(ss, axis)   # [W, k/G]
    out = _dequant_grouped(allq, allsc, G).ravel()[:n]
    return out.reshape(x.shape), new_we, new_se


class CompressedBackend:
    """Out-of-jit backend surface (reference NcclBackend/MpiBackend).

    Holds the persistent worker/server error-feedback buffers per named
    tensor (the reference attaches them to optimizer state; standalone
    callers get the same behavior keyed by `name`).
    """

    def __init__(self, axis: str = "data", mpu=None):
        self.axis = axis
        self._errors = {}
        self._fns = {}  # per-mesh compiled reduction (avoid re-tracing)

    def _get_errors(self, name, shaped_like):
        if name not in self._errors:
            zeros = jnp.zeros(shaped_like.shape, jnp.float32)
            self._errors[name] = (zeros, zeros)
        return self._errors[name]

    def compressed_allreduce(self, tensor, name: str = "default"):
        """Average `tensor`'s per-device shards over the axis with 1-bit
        compression. The input is interpreted as already sharded over
        `axis` on dim 0 (each shard is one worker's contribution)."""
        info = peek_mesh()
        if info is None or self.axis not in info.mesh.shape or \
                info.mesh.shape[self.axis] == 1:
            we, se = self._get_errors(name, tensor)
            out, we, se = compressed_allreduce(tensor, we, se, None)
            self._errors[name] = (we, se)
            return out

        mesh = info.mesh
        we, se = self._get_errors(name, tensor)

        if mesh not in self._fns:
            @partial(jax.shard_map, mesh=mesh,
                     in_specs=(P(self.axis), P(self.axis), P(self.axis)),
                     out_specs=(P(self.axis), P(self.axis), P(self.axis)),
                     check_vma=False)
            def run(x, we, se):
                return compressed_allreduce(x, we, se, self.axis)

            # jit gives shape/dtype-keyed caching: repeated reductions of
            # the same tensor compile once, not once per call
            self._fns[mesh] = jax.jit(run)

        out, we, se = self._fns[mesh](tensor, we, se)
        self._errors[name] = (we, se)
        return out
