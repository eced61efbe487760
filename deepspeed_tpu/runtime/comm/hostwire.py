"""Host-TCP compressed collectives — the second comm substrate.

Reference: deepspeed/runtime/comm/mpi.py (MpiBackend) — the SAME
error-compensated 1-bit algorithm as the NCCL backend, carried by a
second, device-fabric-independent transport. The TPU analogue: XLA
collectives over ICI/DCN are the primary substrate
(runtime/comm/compressed.py); this module carries the identical
algorithm over the jax.distributed coordination service's key-value
store — plain TCP between processes, nothing on the device fabric.

Two things only a host wire can do here:

* a TRUE 1-bit wire format: np.packbits ships 1 bit/element + one fp32
  scale. XLA has no packed-int1 type, so the in-jit sign path travels at
  full width (a measured negative result);
  the reference needed CuPy bit-packing for exactly this
  (deepspeed/runtime/compression/cupy.py) — packbits is its host-side
  twin.
* transport independence: gradients can be reduced even when the device
  fabric is owned by a different collective (e.g. during pipeline
  channel transfers), mirroring how the reference's MPI backend rides
  beside NCCL.

Intended for SMALL, compression-friendly payloads (1-bit/int8 optimizer
wires). The coordinator relays bytes (upload ~1 full payload + 1 owned
chunk per step per rank), so this is a fallback/secondary fabric, not a
bandwidth contender — same positioning as the reference's MPI path.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional, Tuple

import numpy as np

from ..resilience import (fault_point, is_transient_not_timeout,
                          retry_transient)

DEFAULT_TIMEOUT_MS = 120_000

# -- incarnation scoping (elastic shrink-to-survivors restarts) -------------
# The coordination-service KV is write-once per key and a dead rank's
# keys are never cleaned (nobody can know what it posted mid-flight).
# An elastic restart that reuses the SAME coordination service (the
# supervisor relaunches into the same job) would therefore collide with
# — or worse, silently CONSUME — the dead generation's keys: commit-
# barrier done/committed keys (a re-save of the same tag restarts its
# per-process seq counter at 0 in the fresh process), rendezvous
# addresses, gather payloads.  The supervisor exports DSTPU_INCARNATION
# (bumped on every relaunch, elasticity/supervisor.py) and EVERY key on
# this wire is namespaced by it, extending PR 8's generation-scoped
# gathers to the whole KV surface.  Incarnation 0 (no supervisor, or
# the first launch) keeps today's unprefixed keys.

INCARNATION_ENV = "DSTPU_INCARNATION"
_INCARNATION: Optional[int] = None


def incarnation() -> int:
    """The cached incarnation id this process runs as (env-derived;
    engines validate + log it at init via elasticity.elastic_env)."""
    global _INCARNATION
    if _INCARNATION is None:
        raw = os.environ.get(INCARNATION_ENV, "0").strip() or "0"
        try:
            _INCARNATION = max(0, int(raw))
        except ValueError:
            raise ValueError(
                f"hostwire: {INCARNATION_ENV}={raw!r} is not an integer "
                f"— the supervisor exports a numeric relaunch counter; "
                f"a garbled value would silently de-scope every KV key")
    return _INCARNATION


def set_incarnation(n: Optional[int]) -> None:
    """Pin (or with None re-read from env) the incarnation id — engine
    init after validating the elastic env, and tests."""
    global _INCARNATION
    _INCARNATION = None if n is None else max(0, int(n))


def scoped_key(key: str) -> str:
    """Namespace a KV key by the current incarnation.  Applied at every
    client call boundary in this module, so a survivor-generation run
    can never consume (or collide with) a dead generation's write-once
    keys."""
    inc = incarnation()
    return key if inc == 0 else f"dstpu-inc{inc}/{key}"

# -- scaling envelope (documented contract) ---------------------------------
# The KV store relays every value THROUGH the coordinator as one gRPC
# message, so a single huge value both hits the transport's message cap
# (4 MiB default gRPC, raised but not unbounded in the coordination
# service) and serializes the relay.  Payloads above CHUNK_BYTES are
# split into part keys and reassembled on the readers — transparent to
# callers.  Payloads above MAX_PAYLOAD_BYTES are refused loudly: at that
# size the host wire is the wrong substrate (coordinator upload is
# ~W × payload per step), use the XLA-collective backend or shrink the
# wire format (sign instead of int8).
CHUNK_BYTES = 2 << 20          # 2 MiB: safely under gRPC message caps
MAX_PAYLOAD_BYTES = 128 << 20  # 128 MiB/rank/step: the envelope edge


# Client API surface the wire depends on (ADVICE round-5 #4): these are
# asserted at construction so a jax upgrade that renames/removes one
# fails with a versioned message instead of an AttributeError deep
# inside a barrier mid-step.
_REQUIRED_CLIENT_API = ("key_value_set", "blocking_key_value_get",
                        "key_value_delete", "wait_at_barrier")


def _distributed_state():
    """The jax.distributed client state, via the public accessor when
    the installed jax exposes one, else the long-stable private module.
    Returns None when neither shape is recognized (API drift)."""
    import jax

    # newer jax releases export the state object publicly
    state = getattr(jax.distributed, "global_state", None)
    if state is None:
        try:
            from jax._src import distributed

            state = distributed.global_state
        except Exception:
            return None
    if not all(hasattr(state, a) for a in
               ("client", "process_id", "num_processes")):
        return None
    return state


def _client():
    import jax

    state = _distributed_state()
    if state is None:
        raise RuntimeError(
            f"hostwire: jax {jax.__version__} exposes neither "
            "jax.distributed.global_state nor jax._src.distributed."
            "global_state with the expected (client, process_id, "
            "num_processes) surface — the coordination-service KV "
            "transport cannot attach.  Pin a known-good jax or port "
            "runtime/comm/hostwire.py to the new client API.")
    if state.client is None:
        return None, 0, 1
    return state.client, state.process_id, state.num_processes


def _assert_client_api(client) -> None:
    """Fail fast (and versioned) when the KV client lacks a method the
    wire will call later."""
    if client is None:
        return
    import jax

    missing = [a for a in _REQUIRED_CLIENT_API if not hasattr(client, a)]
    if missing:
        raise RuntimeError(
            f"hostwire: the jax {jax.__version__} distributed client is "
            f"missing required method(s) {missing} (has: "
            f"{[a for a in _REQUIRED_CLIENT_API if hasattr(client, a)]}). "
            "The KV wire cannot run on this jax build — pin a version "
            "whose client exposes the full key-value + barrier surface, "
            "or port runtime/comm/hostwire.py.")


def _kv_set(client, key: str, payload: bytes) -> None:
    """Store bytes under `key` via the STRING KV entry points.

    The *_bytes variants segfault in some jaxlib builds (0.4.36
    observed, flat keys included), while key_value_set /
    blocking_key_value_get are stable everywhere — so the wire rides the
    string API with base64 framing.  The 4/3 expansion is priced into
    CHUNK_BYTES: a 2 MiB raw chunk is ~2.7 MiB encoded, still under the
    4 MiB gRPC message cap.

    Transient coordinator faults (UNAVAILABLE, connection reset,
    injected) retry with bounded backoff (runtime/resilience.py).  The
    wire's keys are write-once per (tag, step, gen), so a retry racing
    its own landed first attempt surfaces as ALREADY_EXISTS from the
    real coordination service — that means the value IS durably there,
    i.e. success."""
    import base64

    encoded = base64.b64encode(payload).decode("ascii")
    _kv_set_write_once(client, key, encoded, "hostwire.kv_set")


def _kv_set_write_once(client, key: str, value: str, site: str) -> None:
    """Transient-retried set of a WRITE-ONCE key.  The subtle invariant
    lives here exactly once: ALREADY_EXISTS counts as success ONLY on a
    retry (our own first attempt landed before its ack was lost); on
    the first attempt it means a FOREIGN writer holds the key
    (mis-ranked launch, seq bug) — proceeding would silently serve
    peers someone else's bytes, so that stays a loud failure."""
    attempt = [0]

    skey = scoped_key(key)

    def op():
        attempt[0] += 1
        fault_point(site)
        try:
            client.key_value_set(skey, value)
        except Exception as e:
            if attempt[0] > 1 and \
                    "ALREADY_EXISTS" in str(e).upper().replace(" ", "_"):
                return
            raise

    retry_transient(op, site=f"{site} {key}")


def _kv_put_bytes(client, key: str, payload: bytes,
                  chunk_bytes: int = CHUNK_BYTES) -> None:
    """Store an arbitrary-size byte payload under `key`, chunked into
    part keys so a single value never exceeds the KV relay's message
    envelope (see the scaling-envelope constants above).  The layout
    (`key/n` part count + `key/{i}` parts) matches HostWire's allgather
    framing; `_kv_get_bytes` reassembles.  Write-once semantics per
    part, like every other key on this wire — used by the overlap
    exchange's KV fallback transport (runtime/comm/overlap.py)."""
    cb = int(chunk_bytes)
    nparts = max(1, -(-len(payload) // cb))
    _kv_set(client, f"{key}/n", str(nparts).encode())
    for i in range(nparts):
        _kv_set(client, f"{key}/{i}", payload[i * cb:(i + 1) * cb])


def _kv_get_bytes(client, key: str, timeout_ms: int) -> bytes:
    """Reassemble a `_kv_put_bytes` payload.  One deadline across the
    part gets (the _kv_get discipline): a dead writer surfaces in
    ~timeout_ms regardless of payload size."""
    deadline = time.monotonic() + timeout_ms / 1000.0

    def remaining_ms():
        return max(1, int((deadline - time.monotonic()) * 1000))

    nparts = int(_kv_get(client, f"{key}/n", remaining_ms()))
    return b"".join(_kv_get(client, f"{key}/{i}", remaining_ms())
                    for i in range(nparts))


def _kv_get(client, key: str, timeout_ms: int) -> bytes:
    import base64

    # ONE deadline across retries: a DEADLINE_EXCEEDED first attempt
    # leaves ~nothing for the retries, so retrying a timeout cannot
    # multiply the caller's budget (genuine dead peers still surface in
    # ~timeout_ms); transient transport blips mid-budget retry with the
    # time that is left
    deadline = time.monotonic() + timeout_ms / 1000.0

    skey = scoped_key(key)

    def op():
        fault_point("hostwire.kv_get")
        left = max(1, int((deadline - time.monotonic()) * 1000))
        return base64.b64decode(
            client.blocking_key_value_get(skey, left))

    return retry_transient(op, site=f"hostwire.kv_get {key}")


class KVSignals:
    """Tiny point-to-point signal layer on the coordination-service KV —
    NOT a collective.  Used for per-rank done-keys in the checkpoint
    commit barrier (runtime/checkpointing.CommitBarrier): each process
    posts small string values under explicit keys and any process can
    block on a key appearing.  Values are plain strings (no base64
    framing — signals are tiny and never binary), keys are caller-scoped.

    `_endpoint=(client, rank, world)` drives the signals over a fake
    in-memory KV for tests, like HostWire."""

    def __init__(self, _endpoint=None):
        self.client, self.rank, self.world = (
            _endpoint if _endpoint is not None else _client())
        _assert_client_api(self.client)

    def post(self, key: str, value: str = "1") -> None:
        if self.client is None:
            return
        # write-once semantics shared with the data wire: a retry's
        # ALREADY_EXISTS resolves to success, a first attempt's stays
        # loud (_kv_set_write_once)
        _kv_set_write_once(self.client, key, str(value), "kv.post")

    def wait(self, key: str, timeout_ms: int = DEFAULT_TIMEOUT_MS) -> str:
        if self.client is None:
            raise RuntimeError(
                "KVSignals.wait: no coordination-service client attached "
                "(single-process run?) — nothing ever posts keys here")

        skey = scoped_key(key)

        def op():
            fault_point("kv.wait")
            return self.client.blocking_key_value_get(skey, int(timeout_ms))

        # the blocking timeout IS the dead-peer detector here (commit
        # barrier): transient transport blips retry, deadlines do not —
        # retrying them would multiply commit_timeout_ms and delay the
        # CheckpointIntegrityError the caller exists to raise
        return retry_transient(op, site=f"kv.wait {key}",
                               classify=is_transient_not_timeout)

    def delete(self, key: str) -> None:
        if self.client is None:
            return
        self.client.key_value_delete(scoped_key(key))


class HostWire:
    """Allgather of byte payloads over the coordination-service KV store.

    Every call site must be entered by ALL processes (collective
    contract, like any allreduce). Keys are step-scoped and deleted
    after a barrier, so coordinator memory stays bounded."""

    def __init__(self, tag: str = "dstpu-hostwire",
                 timeout_ms: int = DEFAULT_TIMEOUT_MS,
                 chunk_bytes: int = CHUNK_BYTES,
                 max_payload_bytes: int = MAX_PAYLOAD_BYTES,
                 _endpoint=None):
        # _endpoint=(client, rank, world) lets tests drive the wire over
        # a fake in-memory KV store without jax.distributed processes
        self.client, self.rank, self.world = (
            _endpoint if _endpoint is not None else _client())
        # fail at construction, not deep in a barrier, when the client
        # API surface is incomplete (jax version drift; fakes included)
        _assert_client_api(self.client)
        self.tag = tag
        self.timeout_ms = timeout_ms
        self.chunk_bytes = int(chunk_bytes)
        self.max_payload_bytes = int(max_payload_bytes)
        self._step = 0
        # generation/attempt id scoping the keys of each gather ATTEMPT:
        # bumped whenever a gather fails mid-flight, so a retried gather
        # (or one racing keys stranded by a rank that died between the
        # read and clean barriers — those are never deleted) posts and
        # reads under FRESH keys instead of consuming a dead attempt's
        # payload or colliding with its write-once keys.  Failures are
        # symmetric across ranks (a dead peer times everyone out; an
        # injected fault is scheduled on every rank or surfaces as the
        # others' barrier timeout), so collectively-retried gathers
        # re-agree on the generation.
        self._gen = 0

    def allgather_bytes(self, payload: bytes) -> list:
        """payload from every process, in rank order.

        Payloads above `chunk_bytes` ride multiple part keys (the KV
        relay's message envelope — see module constants); above
        `max_payload_bytes` the call refuses with a clear error instead
        of wedging the coordinator."""
        from ...monitor.counters import COUNTERS

        COUNTERS.add("hostwire.allgather", len(payload))
        fault_point("hostwire.allgather")
        if len(payload) > self.max_payload_bytes:
            raise ValueError(
                f"hostwire payload of {len(payload)} bytes exceeds the "
                f"host-wire envelope ({self.max_payload_bytes} bytes/rank/"
                f"step): the coordination-service KV relay is for SMALL "
                f"compressed payloads — use the XLA-collective backend "
                f"(runtime/comm/compressed.py) or a denser wire format "
                f"for tensors this large")
        if self.client is None or self.world == 1:
            self._step += 1
            return [payload]
        try:
            return self._allgather(payload)
        except BaseException:
            # the attempt died mid-protocol (peer timeout, injected
            # fault, operator interrupt): its keys may be stranded —
            # nobody can safely clean them (a dead rank couldn't have
            # either) — so the NEXT attempt moves to a fresh generation
            self._gen += 1
            raise

    def _allgather(self, payload: bytes) -> list:
        key = f"{self.tag}/{self._step}g{self._gen}"
        cb = self.chunk_bytes
        nparts = max(1, -(-len(payload) // cb))
        _kv_set(self.client, f"{key}/{self.rank}/n",
                str(nparts).encode())
        for i in range(nparts):
            _kv_set(self.client, f"{key}/{self.rank}/{i}",
                    payload[i * cb:(i + 1) * cb])
        # chaos hook for the nastiest window: this rank's payload is up
        # but it dies before the read/clean barriers, stranding keys
        fault_point("hostwire.allgather.posted")
        # ONE deadline for the whole gather: timeout_ms bounds the call,
        # not each of the W x nparts gets (a dead peer must surface in
        # ~timeout_ms regardless of payload size)
        deadline = time.monotonic() + self.timeout_ms / 1000.0

        def remaining_ms():
            return max(1, int((deadline - time.monotonic()) * 1000))

        out = []
        counts = {self.rank: nparts}
        for r in range(self.world):
            if r == self.rank:
                out.append(payload)
                continue
            counts[r] = int(_kv_get(self.client, f"{key}/{r}/n",
                                    remaining_ms()))
            out.append(b"".join(
                _kv_get(self.client, f"{key}/{r}/{i}", remaining_ms())
                for i in range(counts[r])))
        # nobody may delete until everyone has read; nobody may proceed
        # to the NEXT step's set() until this step's keys are gone
        # (barrier ids and deletes carry the same incarnation scope the
        # sets landed under)
        self.client.wait_at_barrier(scoped_key(f"{key}/read"),
                                    self.timeout_ms)
        if self.rank == 0:
            for r in range(self.world):
                self.client.key_value_delete(scoped_key(f"{key}/{r}/n"))
                for i in range(counts[r]):
                    self.client.key_value_delete(
                        scoped_key(f"{key}/{r}/{i}"))
        self.client.wait_at_barrier(scoped_key(f"{key}/clean"),
                                    self.timeout_ms)
        self._step += 1
        return out


def _pack_sign(c: np.ndarray) -> Tuple[bytes, float]:
    """sign-compress: 1 bit/element (bit=1 means +scale) + L1-mean scale."""
    scale = float(np.mean(np.abs(c)))
    return np.packbits(c >= 0).tobytes(), scale


def _unpack_sign(payload: bytes, scale: float, n: int) -> np.ndarray:
    bits = np.unpackbits(np.frombuffer(payload, np.uint8), count=n)
    return np.where(bits.astype(bool), scale, -scale).astype(np.float32)


class HostWireBackend:
    """Out-of-jit compressed-allreduce over the host wire — the same
    surface as CompressedBackend (runtime/comm/compressed.py) and the
    same two-stage error-compensated algorithm as the reference backends
    (deepspeed/runtime/comm/mpi.py:34-290):

      worker: c = x + worker_error; ship sign(c)·scale (packed 1-bit)
      server: rank r owns chunk r of the worker-mean; adds its server
              error, recompresses, ships; everyone reassembles

    wire="sign": 1 bit/element + 4-byte scale per stage (the true 1-bit
    wire). wire="int8": one byte/element + per-group scales (higher
    fidelity, 8x the bytes)."""

    INT8_GROUP = 2048

    def __init__(self, tag: str = "dstpu-onebit", wire: str = "sign",
                 timeout_ms: int = DEFAULT_TIMEOUT_MS,
                 chunk_bytes: int = CHUNK_BYTES,
                 max_payload_bytes: int = MAX_PAYLOAD_BYTES,
                 _endpoint=None):
        if wire not in ("sign", "int8"):
            raise ValueError(f"wire must be 'sign' or 'int8', got {wire!r}")
        self.wire = HostWire(tag=tag, timeout_ms=timeout_ms,
                             chunk_bytes=chunk_bytes,
                             max_payload_bytes=max_payload_bytes,
                             _endpoint=_endpoint)
        self.mode = wire
        self._errors: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}

    @property
    def rank(self):
        return self.wire.rank

    @property
    def world(self):
        return self.wire.world

    # -- int8 helpers (numpy twins of compressed.py's _quant_grouped) ----
    def _quant(self, c: np.ndarray) -> Tuple[bytes, np.ndarray]:
        G = max(1, min(self.INT8_GROUP, c.size))
        pad = (-c.size) % G
        g = np.pad(c, (0, pad)).reshape(-1, G)
        scale = np.max(np.abs(g), axis=-1) / 127.0 + 1e-12
        q = np.clip(np.round(g / scale[:, None]), -127, 127).astype(np.int8)
        return q.tobytes(), scale.astype(np.float32)

    def _dequant(self, payload: bytes, scale: np.ndarray,
                 n: int) -> np.ndarray:
        q = np.frombuffer(payload, np.int8)
        g = q.astype(np.float32).reshape(len(scale), -1)
        return (g * scale[:, None]).ravel()[:n]

    def _compress(self, c: np.ndarray):
        if self.mode == "sign":
            payload, scale = _pack_sign(c)
            return payload, np.float32([scale])
        return self._quant(c)

    def _decompress(self, payload: bytes, scale: np.ndarray,
                    n: int) -> np.ndarray:
        if self.mode == "sign":
            return _unpack_sign(payload, float(scale[0]), n)
        return self._dequant(payload, scale, n)

    def compressed_allreduce(self, tensor, name: str = "default"):
        """Error-compensated compressed MEAN of `tensor` over all
        processes. tensor: host array (np or jax); returns np.float32 of
        the same shape. Must be called collectively."""
        x = np.asarray(tensor, np.float32)
        n = x.size
        W = self.world
        if name not in self._errors:
            self._errors[name] = (np.zeros(n, np.float32),
                                  np.zeros(n, np.float32))
        we, se = self._errors[name]

        # worker stage
        c = x.ravel() + we
        payload, scale = self._compress(c)
        deq_own = self._decompress(payload, scale, n)
        we_new = c - deq_own

        parts = self.wire.allgather_bytes(payload + scale.tobytes())
        sbytes = scale.nbytes
        mean = deq_own.copy()  # own payload already decompressed above
        for r, p in enumerate(parts):
            if r == self.rank:
                continue
            sc = np.frombuffer(p[len(p) - sbytes:], np.float32)
            mean += self._decompress(p[:len(p) - sbytes], sc, n)
        mean /= W

        # server stage: rank r owns chunk r (reference per-rank server
        # error slices, comm/mpi.py server_error)
        chunk = -(-n // W)
        lo, hi = self.rank * chunk, min(n, (self.rank + 1) * chunk)
        out = np.empty(n, np.float32)
        se_new = se.copy()
        if hi > lo:
            s = mean[lo:hi] + se[lo:hi]
            p2, sc2 = self._compress(s)
            se_new[lo:hi] = s - self._decompress(p2, sc2, hi - lo)
            # explicit payload-length prefix: the receiver must not
            # re-derive _quant's group/padding split (ragged last chunk)
            own = len(p2).to_bytes(4, "little") + p2 + sc2.tobytes()
        else:  # more ranks than chunks
            own = b""
        parts2 = self.wire.allgather_bytes(own)
        for r, p in enumerate(parts2):
            rlo, rhi = r * chunk, min(n, (r + 1) * chunk)
            if rhi <= rlo or not p:
                continue
            plen = int.from_bytes(p[:4], "little")
            sc = np.frombuffer(p[4 + plen:], np.float32)
            out[rlo:rhi] = self._decompress(p[4:4 + plen], sc, rhi - rlo)
        self._errors[name] = (we_new, se_new)
        return out.reshape(x.shape)
