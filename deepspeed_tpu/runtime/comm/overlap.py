"""Host-threaded wire exchange: comm/compute overlap for the bucketed
gradient wire and the qwZ parameter gather — now self-healing.

Why a HOST transport and not an XLA restructure: on the XLA:CPU runtime
this repo benches on, collective thunks execute inline in the per-device
thunk sequence — probed exhaustively while building this module: a
collective issued before / interleaved with / data-independent of the
remaining compute runs in exactly the same wall-clock as one issued
after it (fused == barrier-serialized, to the millisecond), and the
gloo wire's time is ~78% CPU-busy (process_time/wall), so even
thread-level concurrency cannot hide it on a saturated box.  What CAN
overlap is a transport whose waits are real OS blocking: raw sockets
move the same payload ~10x cheaper than the in-program collective and
spend most of that in `recv` — idle time the device pipeline runs
straight through.  On TPU fabrics the same schedule-driven structure
lets XLA's latency-hiding scheduler do the overlap in-program; on this
fabric the host exchange IS the overlap mechanism, and the bench
measures the exposure honestly either way.

The pieces:

* `ExchangeTicket` — one in-flight exchange: `wait()` returns the
  rank-ordered `[world, nbytes]` payload matrix and records how long the
  caller was blocked (the EXPOSED wire time the monitor's
  `grad_wire.exposed_ms` counter reports).
* `LocalExchange` — single-process transport: every rank is addressable,
  so the "exchange" is a background-thread materialization of the local
  shards.  The threaded driver machinery (submit/wait ordering, ticket
  lifecycle, teardown) is exactly the multi-process one, so tier-1
  covers it without sockets.
* `SocketExchange` — N-process transport: a full mesh of persistent TCP
  connections (rendezvoused through the coordination-service KV the
  hostwire already rides), one receiver thread per peer demuxing
  sequence-tagged frames, one sender worker serializing submissions in
  order.  Frames are self-describing (per-rank payload table), so the
  receiver needs no topology assumptions.

Self-healing (the fail-fast wire died the moment a peer hiccuped —
erasing the overlap win at fabric scales where link resets are
routine).  Three layers, each bounded and LOUD:

1. **Reconnect + resend.**  Data frames are sequence-tagged and CRC'd;
   the sender retains every frame until each peer ACKs it, and the
   sender worker emits keepalive frames when idle so a dead connection
   surfaces in seconds instead of at the next (possibly far away)
   submit.  A dropped/corrupted connection is torn down and re-dialed
   with bounded exponential backoff (the `retry_transient()` taxonomy's
   RetryPolicy); the rendezvous address keys are GENERATION-scoped
   (`.../g{n}/addr{pid}`) because the coordination KV is write-once — a
   rebound listener publishes its new endpoint under the next
   generation instead of colliding with its old key.  After the
   handshake each side replays exactly the frames the peer never
   acknowledged (`exchange.reconnects` / `exchange.resends` counters).
2. **KV fallback transport.**  When the reconnect budget is exhausted
   (or a peer broadcasts a DEMOTE frame), the exchange stops trusting
   its sockets and serves every in-flight and future payload through
   the coordination-service KV (chunked write-once keys) — training
   stays CORRECT (bitwise: the same bytes reach the same combine
   programs) at degraded speed while the ranks agree on a demotion
   point.
3. **Coordinated demotion.**  `agree_demotion_step()` is the KVSignals-
   style barrier the engine runs at its next step boundary: every rank
   posts the boundary it reached, everyone reads all posts, and the MAX
   is the agreed demotion step — ranks behind it keep training over
   the KV transport until they get there, then every rank tears the
   exchange down and rebuilds its step programs through StepBuilder on
   the serial in-program wire (`exchange.demotions`).

Chaos sites (`runtime/resilience.py` FaultPlan): `exchange.connect`
(dial attempts), `exchange.send` (per peer per data frame),
`exchange.recv` (per received frame), and the payload filter
`exchange.payload` (corrupt rules truncate the received bytes; the CRC
turns that into a connection fault the resend path heals).

Exchanges are identified by a monotonically increasing sequence number.
Every process submits the same exchanges in the same order (the engine
step flow is deterministic across ranks), so a frame's sequence number
alone pairs it with its ticket.
"""

from __future__ import annotations

import queue
import socket
import struct
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ...monitor.counters import COUNTERS
from ..resilience import (RetryPolicy, TransientFault, fault_filter,
                          fault_point, is_transient_not_timeout,
                          retry_transient)
from ...utils.logging import logger

# frame: [ftype u8][seq u64][n_entries u32] then, for DATA frames, per
# entry [nbytes u64][rank u32][crc32 u32] and the concatenated payloads
# in entry order.  ACK frames carry the acked seq and no entries;
# KEEPALIVE/DEMOTE frames carry neither.
_HDR = struct.Struct("<BQI")
_ENT = struct.Struct("<QII")  # (nbytes, rank, crc32)
_HELLO = struct.Struct("<II")  # (pid, flags)

_FT_DATA = 0
_FT_ACK = 1
_FT_KEEPALIVE = 2
_FT_DEMOTE = 3

_HELLO_RECONNECT = 1

_CONNECT_TIMEOUT_S = 60.0
_ACCEPT_TIMEOUT_S = 60.0
# close() join budget per thread; stragglers are LOGGED by name, never
# silently discarded (a leaked receiver pins its socket and its peer)
_CLOSE_JOIN_S = 5.0

DEFAULT_KEEPALIVE_S = 5.0
DEFAULT_RECONNECT_ATTEMPTS = 8
DEFAULT_RECONNECT_WINDOW_S = 60.0


def _now() -> float:
    return time.perf_counter()


class ExchangeBroken(ConnectionError):
    """The exchange exhausted its reconnect budget AND has no KV
    fallback to serve payloads through — in-flight waits cannot
    complete.  The engine surfaces this as a fatal transport failure
    (supervisor-restart territory)."""


class ExchangeTicket:
    """One in-flight exchange.  `wait()` blocks until every expected
    rank's payload has landed and returns the `[world, nbytes]` uint8
    matrix (rank-major).  Timing:

    * `done_at`   when the last payload landed (transport-side stamp)
    * `wait_us`   how long wait() was actually blocked — the caller's
                  EXPOSED wire time (0 when the exchange finished
                  behind compute)
    """

    def __init__(self, seq: int, world: int):
        self.seq = seq
        self.world = world
        self._cond = threading.Condition()
        self._blocks: Dict[int, np.ndarray] = {}
        self._error: Optional[BaseException] = None
        self.created_at = _now()
        self.done_at: Optional[float] = None
        self.wait_us = 0

    # -- transport side -----------------------------------------------

    def post(self, rank: int, block: np.ndarray) -> None:
        with self._cond:
            self._blocks[int(rank)] = block
            if len(self._blocks) >= self.world:
                self.done_at = _now()
            self._cond.notify_all()

    def fail(self, exc: BaseException) -> None:
        with self._cond:
            if self._error is None:
                self._error = exc
            self._cond.notify_all()

    def missing_ranks(self) -> List[int]:
        with self._cond:
            return [r for r in range(self.world) if r not in self._blocks]

    # -- consumer side ------------------------------------------------

    @property
    def ready(self) -> bool:
        with self._cond:
            return self._error is not None or \
                len(self._blocks) >= self.world

    def wait(self, timeout_s: float = 300.0) -> np.ndarray:
        t0 = _now()
        with self._cond:
            deadline = t0 + timeout_s
            while self._error is None and len(self._blocks) < self.world:
                remaining = deadline - _now()
                if remaining <= 0:
                    raise TimeoutError(
                        f"overlap exchange seq={self.seq}: only "
                        f"{sorted(self._blocks)} of {self.world} rank "
                        f"payloads arrived within {timeout_s:.0f}s")
                self._cond.wait(remaining)
            self.wait_us += int((_now() - t0) * 1e6)
            if self._error is not None:
                raise RuntimeError(
                    f"overlap exchange seq={self.seq} failed"
                ) from self._error
            blocks = [self._blocks[r] for r in range(self.world)]
        return np.stack(blocks)


class _ExchangeBase:
    """Shared submit-worker machinery: one persistent worker thread
    materializes each submission's device shards (np.asarray blocks the
    WORKER on the producing program, never the driver) and hands the
    blocks to the transport in submission order.  When the task queue
    is idle the worker emits a liveness tick (`_idle_tick`) every
    `keepalive_s` — the socket transport turns that into keepalive
    frames so a dead connection surfaces between submits."""

    def __init__(self, world: int, keepalive_s: float = DEFAULT_KEEPALIVE_S):
        self.world = int(world)
        self._seq = 0
        self._tasks: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._closed = False
        self._lock = threading.Lock()
        self._keepalive_s = float(keepalive_s)
        # self-healing surface the engine polls at step boundaries
        self.demote_requested = False
        self.broken: Optional[BaseException] = None

    def _ensure_worker(self):
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._worker_loop, name="dstpu-overlap-send",
                daemon=True)
            self._worker.start()

    def _worker_loop(self):
        while True:
            try:
                task = self._tasks.get(timeout=self._keepalive_s)
            except queue.Empty:
                try:
                    self._idle_tick()
                except Exception as e:  # keepalives must never kill send
                    logger.warning(f"overlap exchange keepalive: {e}")
                continue
            if task is None:
                return
            ticket, local_blocks = task
            try:
                blocks = [(rank, np.asarray(get()).view(np.uint8))
                          for rank, get in local_blocks]
            except BaseException as e:  # surfaced at ticket.wait()
                ticket.fail(e)
                continue
            # local blocks land in the ticket BEFORE the network send:
            # they are this process's ground truth, and keeping them
            # valid regardless of transport health is what lets the
            # demotion path settle an interrupted exchange losslessly
            for rank, block in blocks:
                ticket.post(rank, block)
            try:
                self._send(ticket, blocks)
            except BaseException as e:
                self._on_send_failure(ticket, e)

    def _idle_tick(self) -> None:
        """Idle-queue liveness hook (socket transport: keepalives)."""

    def _send(self, ticket: ExchangeTicket,
              blocks: List[Tuple[int, np.ndarray]]) -> None:
        raise NotImplementedError

    def _on_send_failure(self, ticket: ExchangeTicket,
                         exc: BaseException) -> None:
        ticket.fail(exc)

    def submit(self, local_blocks: List[Tuple[int, Callable[[], np.ndarray]]]
               ) -> ExchangeTicket:
        """Start one exchange.  `local_blocks` is [(global_rank, getter)]
        for every rank this process owns; `getter()` returns the rank's
        payload (a device array or shard — materialized on the worker
        thread, so calling submit never blocks on the producing
        program).  Returns the ticket to `wait()` on."""
        if self._closed:
            raise RuntimeError("exchange is closed")
        with self._lock:
            seq = self._seq
            self._seq += 1
            ticket = self._register(seq)
        self._ensure_worker()
        self._tasks.put((ticket, local_blocks))
        return ticket

    def _register(self, seq: int) -> ExchangeTicket:
        return ExchangeTicket(seq, self.world)

    def agree_demotion_step(self, step: int, timeout_ms: int = 120_000
                            ) -> int:
        """Coordinated-demotion barrier: every rank posts the step
        boundary it reached and the MAX across ranks is the agreed
        demotion point.  Single-process: the caller IS every rank."""
        return int(step)

    def threads(self) -> List[threading.Thread]:
        """Live transport threads — registered with the StepWatchdog so
        a hung exchange shows up named in the stall snapshot."""
        return [t for t in (self._worker,) if t is not None and t.is_alive()]

    def _log_leaked(self, threads: List[threading.Thread]) -> None:
        leaked = [t.name for t in threads if t is not None and t.is_alive()]
        if leaked:
            logger.warning(
                f"overlap exchange close: {len(leaked)} thread(s) still "
                f"alive after {_CLOSE_JOIN_S:.0f}s join: {leaked} — a "
                "receiver/sender is wedged (likely blocked in a socket "
                "or device materialization); its resources leak until "
                "process exit")

    def close(self):
        if self._closed:
            return
        self._closed = True
        worker = self._worker
        if worker is not None and worker.is_alive():
            self._tasks.put(None)
            worker.join(timeout=_CLOSE_JOIN_S)
        self._log_leaked([worker])
        self._worker = None


class LocalExchange(_ExchangeBase):
    """Single-process transport: every rank's payload is already
    addressable — the worker thread materializes them and the ticket
    completes.  No sockets, same driver surface (including the chaos
    `exchange.send` site and the demotion flags, so the coordinated-
    demotion engine path is tier-1-testable without processes)."""

    def _send(self, ticket, blocks):
        missing = self.world - len(blocks)
        if missing:
            raise RuntimeError(
                f"LocalExchange: {len(blocks)} local payloads for a "
                f"world of {self.world} — a multi-process mesh needs "
                "the socket transport")
        # transient faults here model a flaky transport hop: absorbed by
        # the bounded-backoff retry exactly like the hostwire KV sites
        retry_transient(lambda: fault_point("exchange.send"),
                        site="overlap exchange send")

    def _on_send_failure(self, ticket, exc):
        if ticket.ready:
            # every rank's payload is already local and posted: nothing
            # was lost — flag coordinated demotion instead of dying
            logger.warning(
                "overlap exchange: send-side fault with all payloads "
                f"local ({type(exc).__name__}: {exc}); requesting "
                "coordinated demotion to the serial wire")
            self.demote_requested = True
            self.broken = exc
        else:
            ticket.fail(exc)


class _PeerConn:
    """One live connection to a peer process."""

    __slots__ = ("sock", "lock", "thread", "gen")

    def __init__(self, sock: socket.socket, gen: int):
        self.sock = sock
        self.lock = threading.Lock()
        self.thread: Optional[threading.Thread] = None
        self.gen = gen


class SocketExchange(_ExchangeBase):
    """N-process transport over a full mesh of persistent TCP
    connections.  Rendezvous rides the coordination-service KV (each
    process publishes `host:port` under a GENERATION-scoped key);
    processes with a lower pid accept, higher pids connect, and the
    hello frame identifies the dialing process (and whether this is a
    reconnect).  One receiver thread per peer demuxes frames by
    sequence number into the matching ticket.

    `_endpoint=(client, pid, nproc)` drives the rendezvous over a fake
    in-memory KV for tests, like HostWire."""

    def __init__(self, world: int, *, tag: str = "ox0",
                 host: Optional[str] = None,
                 keepalive_s: float = DEFAULT_KEEPALIVE_S,
                 reconnect_attempts: int = DEFAULT_RECONNECT_ATTEMPTS,
                 reconnect_window_s: float = DEFAULT_RECONNECT_WINDOW_S,
                 reconnect_policy: Optional[RetryPolicy] = None,
                 _endpoint=None):
        super().__init__(world, keepalive_s=keepalive_s)
        from .hostwire import _client

        if _endpoint is not None:
            self._kv, self.pid, self.nproc = _endpoint
        else:
            import jax

            self.pid = jax.process_index()
            self.nproc = jax.process_count()
            self._kv, _, _ = _client()
        self.tag = tag
        self._scope = f"dstpu/overlap/{tag}"
        self.reconnect_attempts = int(reconnect_attempts)
        self.reconnect_window_s = float(reconnect_window_s)
        self._reconnect_policy = reconnect_policy or RetryPolicy(
            max_attempts=max(1, self.reconnect_attempts),
            base_delay_ms=100.0, max_delay_ms=2000.0, jitter=0.25)

        self._conns: Dict[int, _PeerConn] = {}
        self._conn_epoch: Dict[int, int] = {}  # installs per peer
        self._conn_cv = threading.Condition()
        self._tickets: Dict[int, ExchangeTicket] = {}
        self._tickets_lock = threading.Lock()
        self._stash: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        self._retired_max = -1
        # sender-side resend buffer: seq -> [(rank, block)], retained
        # until every peer ACKed the frame; _unacked tracks who has not
        self._resend: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        self._unacked: Dict[int, set] = {}
        self._resend_lock = threading.Lock()
        self._host = host
        self._gen = 0
        self._peer_gen: Dict[int, int] = {q: 0 for q in range(self.nproc)
                                          if q != self.pid}
        self._kv_mode = False
        self._kv_published: set = set()
        self._kv_thread: Optional[threading.Thread] = None
        self._aux_threads: List[threading.Thread] = []
        self._demote_vote_posted = False
        self._demote_arrive_posted = False

        self._listener: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        try:
            self._bind_listener()
            self._accept_thread = threading.Thread(
                target=self._accept_loop, name="dstpu-overlap-accept",
                daemon=True)
            self._accept_thread.start()

            # higher pids dial lower pids; the hello names the dialer
            for q in range(self.pid):
                s = self._dial(q, reconnect=False)
                self._install_conn(q, s, reconnected=False)
            deadline = time.monotonic() + _ACCEPT_TIMEOUT_S
            with self._conn_cv:
                expected = set(range(self.pid + 1, self.nproc))
                while not expected <= set(self._conns):
                    left = deadline - time.monotonic()
                    if left <= 0:
                        missing = sorted(expected - set(self._conns))
                        raise TimeoutError(
                            f"overlap exchange {tag}: processes {missing} "
                            f"never dialed in within "
                            f"{_ACCEPT_TIMEOUT_S:.0f}s")
                    self._conn_cv.wait(left)
        except BaseException:
            # a half-built mesh must not leak its accept loop, bound
            # listener, or already-installed peer conns — a supervisor
            # catching the init failure and retrying in-process would
            # accumulate one set per attempt
            self.close()
            raise

    # -- rendezvous ---------------------------------------------------

    def _addr_key(self, pid: int, gen: int) -> str:
        return f"{self._scope}/g{gen}/addr{pid}"

    def _bind_listener(self):
        from .hostwire import _kv_set

        self._listener = socket.socket()
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("", 0))
        self._listener.listen(self.nproc)
        port = self._listener.getsockname()[1]
        my_host = self._host or socket.gethostbyname(socket.gethostname())
        # write-once KV: a rebound listener cannot overwrite its old
        # endpoint, so each bind publishes under the NEXT generation
        _kv_set(self._kv, self._addr_key(self.pid, self._gen),
                f"{my_host}:{port}".encode())

    def _dial(self, peer: int, reconnect: bool) -> socket.socket:
        """Connect to `peer` with bounded exponential backoff through
        the transient-fault taxonomy.  Each attempt re-reads the peer's
        generation-scoped address key; a refused connection probes the
        NEXT generation (the peer may have rebound its listener)."""
        from .hostwire import _kv_get

        policy = self._reconnect_policy
        attempts = max(1, self.reconnect_attempts) if reconnect \
            else policy.max_attempts
        # a reconnect's TOTAL budget is the window: it matches the
        # accepting side's re-dial wait, and (unlike attempts x 60 s
        # connect timeouts, which can exceed the ticket deadline) it is
        # sized below overlap_timeout_ms — a blackholed peer must reach
        # the KV fallback + coordinated demotion BEFORE an in-flight
        # ticket's wait fires and kills the run
        deadline = (time.monotonic() + self.reconnect_window_s) \
            if reconnect else None
        last: Optional[BaseException] = None
        for attempt in range(1, attempts + 1):
            if self._closed:
                # bail promptly mid-redial: close() only joins 5 s, and
                # a daemon thread still inside a coordination-KV RPC at
                # interpreter exit aborts the whole process (the peer
                # whose exit dropped this conn often WAS the KV host)
                raise ConnectionError(
                    f"overlap exchange closed while dialing process "
                    f"{peer}") from last
            step_timeout = _CONNECT_TIMEOUT_S
            if deadline is not None:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                step_timeout = min(step_timeout, left)
            try:
                fault_point("exchange.connect")
                addr = _kv_get(
                    self._kv, self._addr_key(peer, self._peer_gen[peer]),
                    int(step_timeout * 1000)).decode()
                h, p = addr.rsplit(":", 1)
                s = socket.create_connection((h, int(p)),
                                             timeout=step_timeout)
                s.settimeout(None)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                s.sendall(_HELLO.pack(
                    self.pid, _HELLO_RECONNECT if reconnect else 0))
                return s
            except (OSError, TransientFault, TimeoutError) as e:
                last = e
                if isinstance(e, ConnectionRefusedError):
                    # the peer may have rebound (new port, next gen)
                    self._probe_peer_gen(peer)
                if attempt >= attempts or (
                        deadline is not None
                        and time.monotonic() >= deadline):
                    break
                delay = policy.delay_s(min(attempt, policy.max_attempts))
                if deadline is not None:
                    delay = min(delay,
                                max(0.0, deadline - time.monotonic()))
                logger.warning(
                    f"overlap exchange: connect to process {peer} failed "
                    f"(attempt {attempt}/{attempts}): "
                    f"{type(e).__name__}: {e}; retrying in "
                    f"{delay * 1000:.0f} ms")
                time.sleep(delay)
        budget = (f"{attempts} attempt(s) / "
                  f"{self.reconnect_window_s:.0f}s window") if reconnect \
            else f"{attempts} attempt(s)"
        raise ConnectionError(
            f"overlap exchange: could not reach process {peer} in "
            f"{budget}") from last

    def _probe_peer_gen(self, peer: int) -> None:
        """A refused dial may mean the peer rebound its listener under
        the next generation key — adopt it when present."""
        from .hostwire import _kv_get

        try:
            _kv_get(self._kv,
                    self._addr_key(peer, self._peer_gen[peer] + 1), 500)
            self._peer_gen[peer] += 1
        except Exception:
            pass

    def _accept_loop(self):
        """Persistent accept thread: initial mesh construction AND
        re-accepts after a drop ride the same listener for the
        exchange's lifetime."""
        while not self._closed:
            try:
                s, _ = self._listener.accept()
            except OSError:
                if self._closed:
                    return
                # the listener itself died: rebind under the next
                # generation so dialers can find the new endpoint
                try:
                    self._gen += 1
                    self._bind_listener()
                    logger.warning(
                        "overlap exchange: listener rebound (generation "
                        f"{self._gen})")
                    continue
                except OSError as e:
                    logger.error(f"overlap exchange: listener rebind "
                                 f"failed: {e}")
                    return
            try:
                s.settimeout(_CONNECT_TIMEOUT_S)
                hello = _read_exact(s, _HELLO.size)
                if hello is None:
                    s.close()
                    continue
                q, flags = _HELLO.unpack(hello)
                s.settimeout(None)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except (OSError, struct.error):
                try:
                    s.close()
                except OSError:
                    pass
                continue
            self._install_conn(q, s, reconnected=bool(
                flags & _HELLO_RECONNECT))

    def _install_conn(self, peer: int, sock: socket.socket,
                      reconnected: bool):
        with self._conn_cv:
            old = self._conns.pop(peer, None)
            # the install epoch is per PEER, not per live conn: the
            # broken conn is popped before its replacement installs, so
            # a conn-local counter would restart and the re-accept
            # waiter could never observe progress
            epoch = self._conn_epoch.get(peer, -1) + 1
            self._conn_epoch[peer] = epoch
            conn = _PeerConn(sock, gen=epoch)
            self._conns[peer] = conn
            self._conn_cv.notify_all()
        if old is not None:
            _close_sock(old.sock)
            self._track_aux(old.thread)
        t = threading.Thread(target=self._recv_loop, args=(peer, conn),
                             name=f"dstpu-overlap-recv{peer}", daemon=True)
        conn.thread = t
        t.start()
        if reconnected:
            COUNTERS.add("exchange.reconnects")
            logger.warning(
                f"overlap exchange: connection to process {peer} "
                f"re-established (conn generation {conn.gen}); replaying "
                "unacknowledged frames")
            self._replay_unacked(peer)
        if self._kv_mode:
            # a peer that connects AFTER the one-shot DEMOTE broadcast
            # (its conn was down, or the broadcast send to it failed)
            # must still learn of the demotion, or it keeps training on
            # sockets while this rank blocks in the demotion barrier
            self._send_frame(peer, self._frame(_FT_DEMOTE, 0))

    # -- frames -------------------------------------------------------

    def _frame(self, ftype: int, seq: int,
               blocks: Optional[List[Tuple[int, np.ndarray]]] = None
               ) -> bytes:
        blocks = blocks or []
        table = b"".join(
            _ENT.pack(b.nbytes, rank, zlib.crc32(b) & 0xFFFFFFFF)
            for rank, b in blocks)
        payload = b"".join(b.tobytes() for _, b in blocks)
        return _HDR.pack(ftype, seq, len(blocks)) + table + payload

    def _send_frame(self, peer: int, frame: bytes) -> bool:
        """One frame to one peer; a failure tears the connection down
        (the reconnect path owns recovery) and returns False — it never
        raises, because the resend buffer still holds the frame."""
        with self._conn_cv:
            conn = self._conns.get(peer)
        if conn is None:
            return False
        try:
            with conn.lock:
                conn.sock.sendall(frame)
            return True
        except (OSError, TransientFault) as e:
            self._mark_conn_broken(peer, conn, e)
            return False

    def _send(self, ticket, blocks):
        # register-then-check ordering matters: _enter_kv_mode snapshots
        # _unacked under _resend_lock after raising the flag, so every
        # seq is either in its snapshot or sees _kv_mode here — never
        # neither (a frame that is neither socket-sent nor KV-published
        # would strand its peers until the ticket timeout)
        with self._resend_lock:
            self._resend[ticket.seq] = blocks
            self._unacked[ticket.seq] = set(self._peer_gen)
        if self._kv_mode:
            self._kv_publish(ticket.seq, blocks)
            # the write-once KV keys are the durable store and no ACKs
            # ride this transport — dropping the registration keeps the
            # resend buffer from growing a full payload per step while
            # ranks behind the demotion target keep training
            with self._resend_lock:
                self._unacked.pop(ticket.seq, None)
                self._resend.pop(ticket.seq, None)
            return
        frame = self._frame(_FT_DATA, ticket.seq, blocks)
        for q in sorted(self._peer_gen):
            try:
                fault_point("exchange.send")
            except BaseException as e:
                with self._conn_cv:
                    conn = self._conns.get(q)
                if conn is not None:
                    self._mark_conn_broken(q, conn, e)
                continue
            self._send_frame(q, frame)

    def _idle_tick(self):
        if self._kv_mode or self._closed:
            return
        frame = self._frame(_FT_KEEPALIVE, 0)
        for q in list(self._peer_gen):
            self._send_frame(q, frame)

    def _replay_unacked(self, peer: int):
        with self._resend_lock:
            todo = sorted(seq for seq, peers in self._unacked.items()
                          if peer in peers)
            frames = [(seq, self._resend[seq]) for seq in todo]
        for seq, blocks in frames:
            nbytes = sum(b.nbytes for _, b in blocks)
            if self._send_frame(peer, self._frame(_FT_DATA, seq, blocks)):
                COUNTERS.add("exchange.resends", nbytes)
                logger.warning(
                    f"overlap exchange: resent frame seq={seq} "
                    f"({nbytes} B) to process {peer}")
            else:
                return  # connection died again; the next install replays

    def _handle_ack(self, peer: int, seq: int):
        with self._resend_lock:
            peers = self._unacked.get(seq)
            if peers is None:
                return
            peers.discard(peer)
            if not peers:
                del self._unacked[seq]
                self._resend.pop(seq, None)

    def _recv_loop(self, peer: int, conn: _PeerConn):
        s = conn.sock
        try:
            while True:
                hdr = _read_exact(s, _HDR.size)
                if hdr is None:
                    if self._closed or self._kv_mode:
                        return
                    raise ConnectionError("peer closed the connection")
                fault_point("exchange.recv")
                ftype, seq, n = _HDR.unpack(hdr)
                if ftype == _FT_ACK:
                    self._handle_ack(peer, seq)
                    continue
                if ftype == _FT_KEEPALIVE:
                    continue
                if ftype == _FT_DEMOTE:
                    self._enter_kv_mode(
                        f"process {peer} requested demotion")
                    continue
                entries = []
                for _ in range(n):
                    nbytes, rank, crc = _ENT.unpack(
                        _read_exact(s, _ENT.size))
                    entries.append((rank, nbytes, crc))
                for rank, nbytes, crc in entries:
                    raw = _read_exact(s, nbytes)
                    raw = fault_filter("exchange.payload", raw)
                    if len(raw) != nbytes or \
                            (zlib.crc32(raw) & 0xFFFFFFFF) != crc:
                        raise ConnectionError(
                            f"corrupt frame seq={seq} rank={rank} from "
                            f"process {peer} ({len(raw)}/{nbytes} B, "
                            "CRC mismatch)")
                    self._route(seq, rank,
                                np.frombuffer(raw, dtype=np.uint8))
                # receipt acknowledged only once every entry verified:
                # the sender may now drop the frame from its buffer
                self._send_frame(peer, self._frame(_FT_ACK, seq))
        except (OSError, ValueError, TypeError, struct.error,
                ConnectionError, TransientFault) as e:
            if not self._closed and not self._kv_mode:
                self._mark_conn_broken(peer, conn, e)

    # -- connection failure / healing ---------------------------------

    def _mark_conn_broken(self, peer: int, conn: _PeerConn,
                          exc: BaseException):
        with self._conn_cv:
            if self._conns.get(peer) is not conn:
                return  # already replaced by a newer connection
            del self._conns[peer]
        _close_sock(conn.sock)
        # keep the dead conn's receiver tracked: close() must join it
        # and LOG it by name if it is wedged (a recv blocked on an fd
        # closed out from under it never wakes), never silently drop it
        self._track_aux(conn.thread)
        if self._closed or self._kv_mode:
            return
        logger.warning(
            f"overlap exchange: connection to process {peer} dropped "
            f"({type(exc).__name__}: {exc}); "
            + ("re-dialing with bounded backoff" if peer < self.pid
               else "awaiting the peer's re-dial"))
        if peer < self.pid:
            t = threading.Thread(target=self._reconnect, args=(peer,),
                                 name=f"dstpu-overlap-redial{peer}",
                                 daemon=True)
        else:
            t = threading.Thread(target=self._await_reaccept,
                                 args=(peer, conn.gen),
                                 name=f"dstpu-overlap-await{peer}",
                                 daemon=True)
        self._track_aux(t)
        t.start()

    def _track_aux(self, t: Optional[threading.Thread]) -> None:
        """Track a service thread no longer owned by a live connection
        (dead conns' receivers, redial/await workers) so close() joins
        it and the watchdog's thread report sees it."""
        if t is None or t is threading.current_thread():
            return
        with self._conn_cv:
            self._aux_threads = [a for a in self._aux_threads
                                 if a.is_alive() and a is not t]
            if t.is_alive() or not t.ident:
                self._aux_threads.append(t)

    def _reconnect(self, peer: int):
        if self.reconnect_attempts <= 0:
            self._declare_broken(ConnectionError(
                "reconnection disabled (overlap_reconnect_attempts=0)"))
            return
        try:
            s = self._dial(peer, reconnect=True)
        except BaseException as e:
            self._declare_broken(e)
            return
        if self._closed or self._kv_mode:
            _close_sock(s)
            return
        # _install_conn counts this side's exchange.reconnects and
        # replays our unacked frames; the acceptor side does the same
        # when it sees the reconnect hello
        self._install_conn(peer, s, reconnected=True)

    def _await_reaccept(self, peer: int, old_gen: int):
        deadline = time.monotonic() + self.reconnect_window_s
        with self._conn_cv:
            while True:
                conn = self._conns.get(peer)
                if conn is not None and conn.gen > old_gen:
                    return  # the peer re-dialed; _install_conn replayed
                if self._closed or self._kv_mode:
                    return
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._conn_cv.wait(left)
        self._declare_broken(ConnectionError(
            f"process {peer} did not re-dial within "
            f"{self.reconnect_window_s:.0f}s"))

    def _declare_broken(self, exc: BaseException):
        """Reconnect budget exhausted: fall back to the KV transport
        (correct, slower) and flag coordinated demotion; with no KV
        client there is nothing to serve payloads through — fail every
        in-flight ticket loudly."""
        if self._closed:
            return
        if self._kv is not None:
            self._enter_kv_mode(
                f"reconnect budget exhausted ({type(exc).__name__}: "
                f"{exc})", exc)
            return
        self.broken = exc
        with self._tickets_lock:
            tickets = list(self._tickets.values())
        err = ExchangeBroken(
            f"overlap exchange is down and has no KV fallback: {exc}")
        err.__cause__ = exc
        for t in tickets:
            t.fail(err)

    # -- KV fallback transport ----------------------------------------

    def _demote_pending_key(self) -> str:
        return f"{self._scope}/demote/pending"

    def poll_peer_demotion(self) -> bool:
        """Cheap pre-dispatch probe the engine runs while this exchange
        is unhealthy (a peer connection is down): a peer that entered
        the KV fallback posted the demote-pending key the moment it did.
        Learning about it BEFORE dispatching the next step's programs
        closes a real deadlock: that peer parks in the demotion barrier
        at its step boundary and never joins this step's in-program
        collectives, so a rank that dispatches first blocks in a psum
        until the barrier timeout.  Healthy mesh or already-flagged:
        no KV traffic."""
        if self.demote_requested or self._closed:
            return self.demote_requested
        with self._conn_cv:
            if len(self._conns) == len(self._peer_gen):
                return False  # all conns up — nothing to suspect
        from .hostwire import _kv_get

        try:
            raw = _kv_get(self._kv, self._demote_pending_key(), 50)
        except Exception:
            return False  # not posted (or a KV hiccup): keep training
        self._enter_kv_mode("peer demotion pending: "
                            + raw.decode("utf-8", "replace"))
        return True

    def _enter_kv_mode(self, reason: str,
                       exc: Optional[BaseException] = None):
        with self._conn_cv:
            if self._kv_mode or self._closed:
                return
            self._kv_mode = True
            self.demote_requested = True
            if exc is not None:
                self.broken = exc
            conns = list(self._conns.items())
            self._conn_cv.notify_all()
        logger.warning(
            f"overlap exchange: {reason} — switching to the "
            "coordination-KV fallback transport and requesting "
            "coordinated demotion to the serial wire (training stays "
            "bitwise; throughput degrades until the ranks agree)")
        # durable fast flag for peers whose conn to us is already gone
        # (the DEMOTE frame below only reaches live conns): their
        # pre-dispatch poll_peer_demotion() picks this up
        from .hostwire import _kv_set

        try:
            _kv_set(self._kv, self._demote_pending_key(),
                    reason.encode()[:256])
        except Exception:
            pass  # another rank posted first — same signal
        # tell every still-reachable peer, then serve everything a peer
        # might still be missing through write-once KV keys
        demote = self._frame(_FT_DEMOTE, 0)
        for q, _ in conns:
            # a failed send scraps the dead conn (_send_frame marks it
            # broken) so a later re-accept installs a fresh one —
            # _install_conn re-sends DEMOTE to it
            self._send_frame(q, demote)
        with self._resend_lock:
            outstanding = sorted(self._unacked)
            frames = [(seq, self._resend[seq]) for seq in outstanding]
        for seq, blocks in frames:
            self._kv_publish(seq, blocks)
        with self._resend_lock:
            for seq, _ in frames:
                self._unacked.pop(seq, None)
                self._resend.pop(seq, None)
        self._kv_thread = threading.Thread(
            target=self._kv_fetch_loop, name="dstpu-overlap-kvfetch",
            daemon=True)
        self._kv_thread.start()

    def _kv_publish(self, seq: int, blocks: List[Tuple[int, np.ndarray]]):
        from .hostwire import _kv_put_bytes

        for rank, b in blocks:
            key = (seq, int(rank))
            # claim atomically: the sender worker (kv-mode _send) and
            # the healer thread (_enter_kv_mode's outstanding replay)
            # can race on the same seq, and a duplicate put on the
            # write-once KV key is a LOUD failure — exactly one side
            # may publish each (seq, rank)
            with self._resend_lock:
                if key in self._kv_published:
                    continue
                self._kv_published.add(key)
            _kv_put_bytes(self._kv, f"{self._scope}/kvx/s{seq}/r{rank}",
                          b.tobytes())

    def _kv_fetch_loop(self):
        from .hostwire import _kv_get_bytes

        while not self._closed:
            with self._tickets_lock:
                live = sorted(self._tickets.items())
            progressed = False
            for seq, ticket in live:
                for r in ticket.missing_ranks():
                    if self._closed:
                        return
                    try:
                        raw = _kv_get_bytes(
                            self._kv, f"{self._scope}/kvx/s{seq}/r{r}",
                            2000)
                    except Exception:
                        continue  # not posted yet; retry next sweep
                    ticket.post(r, np.frombuffer(raw, dtype=np.uint8))
                    progressed = True
            if not progressed:
                time.sleep(0.05)

    def agree_demotion_step(self, step: int, timeout_ms: int = 120_000
                            ) -> Optional[int]:
        """Non-parking demotion agreement (engine, at step boundaries).

        A naive blocking barrier here deadlocks the mesh: a rank that
        parks waiting for peers stops dispatching device programs, and
        a peer that was already mid-step blocks forever inside an
        in-program collective the parked rank never joins (observed on
        the 2-proc TCP campaign, both orderings).  Instead:

        1. VOTE: post this rank's first flagged boundary under a
           write-once key, then read every rank's vote NON-blocking.
           Any vote missing -> return None: the engine keeps training
           (the KV fallback transport stays bitwise) and retries at the
           next boundary — nobody ever parks while a peer might still
           be mid-dispatch.
        2. TARGET = max(votes) + 1.  The +1 means every vote is a full
           step old (posted at or before boundary max(votes)) by the
           time any rank reaches the target, so all ranks compute the
           SAME target from the same frozen write-once set.
        3. ARRIVE: a rank at the target posts an arrival key and
           blocking-reads every rank's arrival.  Parking here is safe:
           this rank has dispatched every program up to the target, so
           all peers can reach the target without it.  Returns
           max(arrivals) — the step every rank demotes at together.

        The blocking phase is bounded by timeout_ms (shared deadline,
        deadline-exceeded NOT retried: the barrier timeout IS the
        dead-peer detector, the KVSignals.wait precedent)."""
        from .hostwire import _kv_get, _kv_set

        b = int(step)
        if not self._demote_vote_posted:
            try:
                _kv_set(self._kv,
                        f"{self._scope}/demote/vote/r{self.pid}",
                        str(b).encode())
            except Exception:
                pass  # a crash-relaunch may find its old vote: same value
            self._demote_vote_posted = True
        votes = []
        for q in range(self.nproc):
            try:
                votes.append(int(_kv_get(
                    self._kv, f"{self._scope}/demote/vote/r{q}", 50)))
            except Exception:
                return None  # a rank has not flagged yet — keep training
        target = max(votes) + 1
        if b < target:
            return target
        if not self._demote_arrive_posted:
            try:
                _kv_set(self._kv,
                        f"{self._scope}/demote/arrive/r{self.pid}",
                        str(b).encode())
            except Exception:
                pass
            self._demote_arrive_posted = True
        deadline = time.monotonic() + timeout_ms / 1000.0

        def read(q: int):
            # raw read (values ride the wire base64'd, like _kv_get, and
            # the key carries the same incarnation scope _kv_set wrote
            # it under): remaining time recomputed per attempt from ONE
            # shared deadline, and deadline-exceeded NOT retried
            import base64

            from .hostwire import scoped_key

            left = max(1, int((deadline - time.monotonic()) * 1000))
            return base64.b64decode(self._kv.blocking_key_value_get(
                scoped_key(f"{self._scope}/demote/arrive/r{q}"), left))

        final = target
        for q in range(self.nproc):
            if q == self.pid:
                continue
            val = retry_transient(lambda q=q: read(q),
                                  site=f"exchange.demote r{q}",
                                  classify=is_transient_not_timeout)
            final = max(final, int(val))
        return final

    # -- ticket routing / lifecycle -----------------------------------

    def _register(self, seq: int) -> ExchangeTicket:
        ticket = ExchangeTicket(seq, self.world)
        with self._tickets_lock:
            self._tickets[seq] = ticket
            for rank, block in self._stash.pop(seq, []):
                ticket.post(rank, block)
        return ticket

    def _route(self, seq: int, rank: int, block: np.ndarray):
        with self._tickets_lock:
            t = self._tickets.get(seq)
            if t is None:
                if seq <= self._retired_max:
                    return  # duplicate of an already-combined frame
                # frame arrived before submit() registered the ticket
                self._stash.setdefault(seq, []).append((rank, block))
                return
        t.post(rank, block)

    def retire(self, ticket: ExchangeTicket):
        """Drop a completed ticket's registration (the engine retires
        tickets after combining, bounding the map to in-flight ones)."""
        with self._tickets_lock:
            self._tickets.pop(ticket.seq, None)
            if ticket.seq > self._retired_max:
                self._retired_max = ticket.seq

    def threads(self) -> List[threading.Thread]:
        with self._conn_cv:
            recv = [c.thread for c in self._conns.values()]
        cand = ([self._worker, self._accept_thread, self._kv_thread]
                + recv + list(self._aux_threads))
        return [t for t in cand if t is not None and t.is_alive()]

    def close(self):
        was_closed = self._closed
        super().close()
        if was_closed:
            return
        if self._listener is not None:
            _close_sock(self._listener)
        with self._conn_cv:
            conns = list(self._conns.values())
            self._conns.clear()
            self._conn_cv.notify_all()
        for c in conns:
            _close_sock(c.sock)
        join = [self._accept_thread, self._kv_thread] + \
            [c.thread for c in conns] + list(self._aux_threads)
        for t in join:
            # a re-dial worker is tracked before it is started (`ident`
            # None until then); started later, it sees `_closed` and ends
            if t is not None and t.ident is not None \
                    and t is not threading.current_thread():
                t.join(timeout=_CLOSE_JOIN_S)
        self._log_leaked([t for t in join
                          if t is not threading.current_thread()])
        self._aux_threads = []
        self._kv_thread = None
        # drop the payload buffers: a demoted engine keeps the process
        # alive long after this close, and these can hold a gradient
        # payload per in-flight step
        with self._resend_lock:
            self._resend.clear()
            self._unacked.clear()
            self._kv_published.clear()
        with self._tickets_lock:
            self._stash.clear()


def _close_sock(s) -> None:
    try:
        s.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        s.close()
    except OSError:
        pass


def _read_exact(s: socket.socket, n: int) -> Optional[bytes]:
    parts = []
    got = 0
    while got < n:
        chunk = s.recv(min(1 << 20, n - got))
        if not chunk:
            if parts:  # EOF mid-frame: the peer died mid-send
                raise ConnectionError("peer closed mid-frame")
            return None  # clean EOF at a frame boundary (shutdown)
        parts.append(chunk)
        got += len(chunk)
    return b"".join(parts)


_EXCHANGE_SEQ = [0]


def make_exchange(world: int, tag: Optional[str] = None, **kwargs):
    """The right transport for the current topology: sockets across
    processes, the in-process fast path otherwise.  Each construction
    gets a fresh rendezvous tag (the coordination KV is write-once and
    engine construction order is identical on every process, so the
    per-process counter agrees globally).  `kwargs` (keepalive_s,
    reconnect_attempts, reconnect_window_s) tune the self-healing
    machinery; the engine derives them from the comm config."""
    import jax

    if jax.process_count() > 1:
        if tag is None:
            tag = f"ox{_EXCHANGE_SEQ[0]}"
            _EXCHANGE_SEQ[0] += 1
        return SocketExchange(world, tag=tag, **kwargs)
    return LocalExchange(world,
                         keepalive_s=kwargs.get("keepalive_s",
                                                DEFAULT_KEEPALIVE_S))
