"""Trace timelines: a bounded, sampled span recorder + serving SLO
windows (TPU addition — no reference analogue; the reference's timeline
story is external profilers).

`TraceRecorder` answers *where the time went* on a per-rank timeline:
structured span events (input host waits, grads dispatches, exposed
exchange waits, apply, ckpt stalls, autotune probes, per-request serving
lifecycle) land in a rank-local `trace.rank*.jsonl` inside the monitor
run dir.  `tools/trace_report.py` merges all ranks into one
Chrome/Perfetto trace-event JSON (pid=rank, tid=subsystem) with
cross-rank clock-skew alignment estimated over the hostwire KV at init.

Always-on-safe by construction:

  * off by default — the recorder only exists when
    `"monitor": {"tracing": {"enabled": true}}`; disabled runs create
    zero files and zero threads, and no instrumentation site ever
    synchronizes a device value (dispatch-side walls only), so traced
    and untraced runs are bitwise identical.
  * sampled — `sample_rate` gates whole steps / requests through a
    seeded hash (deterministic: same seed + schedule => the same event
    sequence, the FaultPlan convention).
  * byte-bounded — the rank file stops growing at `max_file_bytes`
    (dropped writes are counted, never raised).
  * ring-buffered — the last `buffer_events` events survive in memory
    regardless of the file cap; `StepWatchdog` dumps this flight
    recorder into its trip snapshot so a wedged step ships a timeline.

Counters (µs-in-bytes convention does NOT apply here — these are real
bytes/calls): `trace.events` (calls=events recorded, bytes=bytes
written), `trace.dropped` (calls=events the byte cap rejected),
`slo.windows` (calls=slo events emitted).

`phase` is the one call site an engine's host thread uses to say what it
is doing: it opens a `jax.profiler.TraceAnnotation`, so any profiler
capture (`monitor.TraceWindow`, the benchmark's `--trace 1`) shows the
interval on the host plane of the same `.xplane.pb` as the device's
operations, and hands the same interval to a recorder when one is given.
The serving loop's `serve.*` and the training dispatch's `train.*` phases
go through it (docs/tutorials/tracing.md lists them).

`program_scopes` is how a `jax.named_scope` reaches a reader of the
DEVICE trace.  The profiler's `XLA Ops` events carry an instruction's HLO
text and its device time and nothing of the scope it was written under;
the compiled program does (`metadata={op_name="jit(decode)/attn/..."}`),
and an event's text begins with the instruction's name, unique in its
module.  So the program says the map — `ServeEngine.attach_tracing`
records one `program_scopes` instant a compiled program, packed by
`pack_scopes` — and `device_scope_times` joins it with a profile:
device time by scope path, each nanosecond counted once
(`tools/trace_report.py --xplane`; the benchmark's
`readers/trace_scope_time.py` is the same join over its own reduction
of the trace and imports nothing from here).

`ServingSLO` rides the same clock: a sliding window over request
lifecycle observations (TTFT, emitted tokens, queue depth, speculative
accepts, sheds) emitting periodic `slo` monitor events; the p50/p99
are NEAREST-RANK percentiles — the exact definition serve_bench pins —
so the report's "Serving SLO" section reproduces the bench's numbers
when the window covers the lane.
"""

from __future__ import annotations

import bisect
import collections
import json
import re
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from .counters import COUNTERS

TRACE_SCHEMA_VERSION = 1
TRACE_FILE_PREFIX = "trace.rank"

# subsystem categories (the merged trace's tid lanes)
TRACE_CATEGORIES = ("train", "input", "wire", "ckpt", "autotune",
                    "watchdog", "serve", "slo")


_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = (?P<rest>.*)$')
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="(?P<op>[^"]*)"')
_OPCODE = re.compile(r'\s*([\w\-]+)\(')
_OPERAND = re.compile(r'%([\w.\-]+)')
_WRAPPER = re.compile(r'^p?jit\(.*\)$')
_EVENT_NAME = re.compile(r'^%?([\w.\-]+) = ')
_DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
# instructions that never run as an operation of their own
_NO_EVENT = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast"}


def _opcode(rest: str) -> str:
    """`fusion` from `f32[8]{0} fusion(...)`: what follows the result's
    shape, which is one word or, for a tuple, a parenthesis."""
    end, depth = rest.find(" "), 0
    if rest.startswith("("):
        for end, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if not depth:
                break
        end += 1
    m = _OPCODE.match(rest, end)
    return m.group(1) if m else ""


def _own_path(rest: str) -> Optional[str]:
    """The scope path an instruction's own `op_name` gives, or None."""
    op = _OP_NAME.search(rest)
    if op is None:
        return None
    # (a merged instruction carries its parts' names joined by ";": the
    # first is its own)
    parts = op.group("op").split(";", 1)[0].split("/")
    if not _WRAPPER.match(parts[0]):
        return None
    return "/".join(c for c in parts[1:-1] if not _WRAPPER.match(c))


def _moved_paths(insts, paths) -> Dict[str, str]:
    """Paths for one computation's instructions that have none of their
    own, `insts` [(name, opcode, operands)] in the schedule's order: the
    path of the first instruction, by that order, that consumes what the
    instruction made (through any others without a path), else of the
    last operand that has one; with `xla.<opcode>` appended."""
    at = {name: i for i, (name, _, _) in enumerate(insts)}
    users = [[] for _ in insts]
    for i, (_, _, operands) in enumerate(insts):
        for o in operands:
            if o in at and at[o] < i:
                users[at[o]].append(i)
    first = [None] * len(insts)    # index of the first consumer with a path
    for i in range(len(insts) - 1, -1, -1):
        if insts[i][0] in paths:
            first[i] = i
        else:
            first[i] = min((first[u] for u in users[i]
                            if first[u] is not None), default=None)
    made_by, out = {}, {}    # made_by: the path of what produced a value
    for i, (name, opcode, operands) in enumerate(insts):
        if name in paths:
            made_by[name] = paths[name]
            continue
        made_by[name] = next((made_by[o] for o in reversed(operands)
                              if made_by.get(o)), "")
        path = paths[insts[first[i]][0]] if first[i] is not None \
            else made_by[name]
        if path and opcode not in _NO_EVENT:
            out[name] = f"{path}/xla.{opcode}"
    return out


def program_scopes(compiled_text: str) -> Dict[str, str]:
    """{instruction name: scope path} of a compiled program's text
    (`jitted.lower(...).compile().as_text()`), over every instruction of
    every computation of the module: its `op_name` without the `jit(...)`
    / `pjit(...)` wrappers and without the trailing primitive —
    `jit(decode)/attn/full_attend/kernel.grouped_attention/jit(_walk)/
    pallas_call` is `attn/full_attend/kernel.grouped_attention`, and
    `jit(decode)/add`, written under no scope, is "".  An `op_name` that
    does not begin with the program's own `jit(...)` is no path (a
    parameter carries its argument's name, the body of a reduction the
    bare `reduce_sum`).

    An instruction the COMPILER made has no `op_name`: the copies and
    slices that move a weight into fast memory ahead of its use
    (`copy-start` / `copy-done`, `slice-start` / `slice-done`, a
    `ConcatBitcast`), whose `-done` halves are where a program waits for
    its data — a fifth of a decode step of GPT-2 xl.  Such an
    instruction is counted with what it serves: the path of the first
    instruction, in the schedule's order, that consumes what it made,
    else of what it consumed, with one component `xla.<opcode>` appended
    (`attn/paged_attend/xla.slice-done`); left out where neither has a
    path."""
    out, insts = {}, []

    def close():
        out.update(_moved_paths(insts, out))
        insts.clear()

    for line in compiled_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            if line.startswith("}"):
                close()
            continue
        name, rest = m.group("name"), m.group("rest")
        path = _own_path(rest)
        if path is not None:
            out[name] = path
        insts.append((name, _opcode(rest), _OPERAND.findall(rest)))
    close()
    return out


def program_name(compiled_text: str) -> str:
    """`jit_decode` from `HloModule jit_decode, ...`: what the trace's
    `XLA Modules` line prints before the parenthesis."""
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", compiled_text)
    return m.group(1) if m else ""


def pack_scopes(scopes: Dict[str, str]) -> Dict[str, Any]:
    """The map as an event carries it: the distinct paths in a table and
    each instruction as an index into it."""
    paths = sorted(set(scopes.values()))
    at = {p: i for i, p in enumerate(paths)}
    return {"paths": paths,
            "instructions": {k: at[v] for k, v in scopes.items()}}


def unpack_scopes(args: Dict[str, Any]) -> Dict[str, str]:
    paths = args["paths"]
    return {k: paths[i] for k, i in args["instructions"].items()}


def scope_maps(events) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: path}} from a run's `program_scopes`
    events (the newest of a program wins)."""
    return {e["args"]["program"]: unpack_scopes(e["args"])
            for e in events if e.get("name") == "program_scopes"}


def stage_of(path: str, stages) -> str:
    """The one of `stages` (a program's top-level scopes: `serving/
    programs.py::STAGES`) a scope path lies under; "" for a path under
    none."""
    head = path.split("/", 1)[0]
    return head if head in stages else ""


def load_profile(path: str):
    """`jax.profiler.ProfileData` of an `.xplane.pb` or `.xplane.pb.gz`."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def device_scope_times(profile, events, window=None) -> Dict[str, Any]:
    """Device time by scope path for every program that has a
    `program_scopes` event among `events`, from a profile
    (`jax.profiler.ProfileData` of an `.xplane.pb`).  -> {program:
    {"runs": n, "run_ns": mean device duration of a run, "paths": {path:
    ns a run}, "unscoped": {instruction: ns a run}}}.  A run is an `XLA
    Modules` event of the program that lies wholly inside `window` ((lo,
    hi) ns; None: the whole profile); its operations are the `XLA Ops`
    events of the same device that start inside it.  Each nanosecond is
    counted once: where one event lies inside another (a `while` and its
    body) the innermost event whose instruction has a path takes the
    time and the outer one keeps the rest; time under no path — an
    instruction the map lacks, or one written under no scope — is path
    "", and `unscoped` says whose it was."""
    maps = scope_maps(events)
    acc = {p: {"runs": 0, "run_ns": 0, "paths": collections.Counter(),
               "unscoped": collections.Counter()} for p in maps}
    lo, hi = window or (float("-inf"), float("inf"))
    for plane in profile.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                            e.name) for e in ln.events]
                 for ln in plane.lines
                 if ln.name in ("XLA Ops", "XLA Modules")}
        runs = sorted((a, b, name.split("(", 1)[0])
                      for a, b, name in lines.get("XLA Modules", ())
                      if a >= lo and b <= hi)
        starts = [r[0] for r in runs]
        for a, b, program in runs:
            if program in acc:
                acc[program]["runs"] += 1
                acc[program]["run_ns"] += b - a
        stack, t = [], 0    # (end, program, path, instruction), open events

        def credit(until):
            if stack and until > t and stack[-1][1] in acc:
                _, program, path, name = stack[-1]
                acc[program]["paths"][path] += until - t
                if not path:
                    acc[program]["unscoped"][name] += until - t

        for a, b, text in sorted(lines.get("XLA Ops", ()),
                                 key=lambda e: (e[0], -e[1])):
            while stack and stack[-1][0] <= a:
                credit(stack[-1][0])
                t = max(t, stack.pop()[0])
            credit(a)
            t = a
            i = bisect.bisect_right(starts, a) - 1
            program = runs[i][2] if i >= 0 and a < runs[i][1] else None
            m = _EVENT_NAME.match(text)
            name = m.group(1) if m else text[:40]
            path = maps.get(program, {}).get(name, "")
            if stack:   # an inner event without a path is its outer one's
                b = min(b, stack[-1][0])
                if not path:
                    _, program, path, name = stack[-1]
            stack.append((b, program, path, name))
        while stack:
            credit(stack[-1][0])
            t = max(t, stack.pop()[0])
    for prog in acc.values():
        n = max(prog["runs"], 1)
        prog["run_ns"] /= n
        for key in ("paths", "unscoped"):
            prog[key] = {k: v / n for k, v in prog[key].items()}
    return acc


def scope_table(times: Dict[str, Any], stages) -> List[str]:
    """`device_scope_times` as the lines `tools/trace_report.py --xplane`
    prints: for each program its runs and their mean device time, then
    ms a run by stage (`stages`, in their order) and by every scope
    beneath one that holds a thousandth of the run or more — the
    layers' own scopes, the registry's `kernel.<op>`, the compiler's
    `xla.<opcode>` — what no stage owns last, with the instructions that
    make up most of it."""
    out = []
    for program, got in times.items():
        if not got["runs"]:
            out.append(f"{program}: no run in the profile")
            continue
        out.append(f"{program}: {got['runs']} runs, "
                   f"{got['run_ns'] / 1e6:.3f} ms a run on the device")
        by = collections.Counter()
        for path, ns in got["paths"].items():
            parts = tuple(path.split("/")) if stage_of(path, stages) else ()
            for d in range(min(len(parts), 1), len(parts) + 1):
                by[parts[:d]] += ns
        for key in sorted(by, key=lambda k: (
                not k, stages.index(k[0]) if k else 0, k)):
            if len(key) > 1 and by[key] < 1e-3 * got["run_ns"]:
                continue    # a scope under a thousandth of the run
            label = "  " * max(len(key) - 1, 0) + (
                key[-1] if key else "(no stage)")
            out.append(f"  {label:<44}{by[key] / 1e6:>10.3f} ms "
                       f"{100 * by[key] / got['run_ns']:>6.1f} %")
        worst = sorted(got["unscoped"].items(), key=lambda kv: -kv[1])[:5]
        for name, ns in worst:
            out.append(f"      {name:<40}{ns / 1e6:>10.3f} ms")
        idle = got["run_ns"] - sum(got["paths"].values())
        out.append(f"  {'(no operation)':<44}{idle / 1e6:>10.3f} ms "
                   f"{100 * idle / got['run_ns']:>6.1f} %")
    return out


def percentile_nearest_rank(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an ALREADY-SORTED list — the same
    definition tools/serve_bench.py pins for its TTFT table, duplicated
    here so the SLO window reproduces the bench bit-for-bit."""
    if not sorted_vals:
        return 0.0
    import math

    k = max(0, math.ceil(q / 100.0 * len(sorted_vals)) - 1)
    return sorted_vals[min(k, len(sorted_vals) - 1)]


def _sample_hash(seed: int, key) -> float:
    """Deterministic [0, 1) hash of (seed, key) — crc32, stable across
    processes and runs (unlike hash())."""
    return zlib.crc32(f"{seed}:{key}".encode()) / 2**32


class _SpanCtx:
    """Context manager recording one complete ("X") event on exit."""

    __slots__ = ("_rec", "_name", "_cat", "_args", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, cat: str,
                 args: Dict[str, Any]):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = rec.now_us()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        self._rec.add_complete(self._name, self._cat, ts_us=t0,
                               dur_us=self._rec.now_us() - t0,
                               **self._args)
        return False


class _RecordedPhase(_SpanCtx):
    """A `phase` with a recorder: the annotation is open for exactly the
    interval the recorder's event covers."""

    __slots__ = ("_ann",)

    def __init__(self, ann: str, rec: "TraceRecorder", name: str,
                 cat: str, args: Dict[str, Any]):
        self._ann = TraceAnnotation(ann)
        super().__init__(rec, name, cat, args)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = self._rec.now_us()
        return self

    def __exit__(self, *exc):
        super().__exit__(*exc)
        return self._ann.__exit__(*exc)


def phase(name: str, recorder: Optional["TraceRecorder"] = None,
          cat: str = "serve", span: Optional[str] = None, **args):
    """What an engine's host thread is doing from here to the end of the
    `with`, to two sinks from one call site: a profiler annotation
    `name`, always (0.5 µs where no profiler session runs), and, when
    `recorder` is given — the caller passes it only where the step or
    request is sampled — the same interval as a complete event named
    `span` (default `name`) in the recorder.  Host walls only: a phase
    never waits for a device value its body did not wait for."""
    if recorder is None:
        return TraceAnnotation(name)
    return _RecordedPhase(name, recorder, span or name, cat, args)


class TraceRecorder:
    """Bounded span recorder; one per rank, owned by RunMonitor.

    `wire`: an optional HostWire — when given, construction performs ONE
    collective allgather so every rank captures its (wall, mono) clock
    pair at an approximately simultaneous instant; the merger aligns
    rank timelines on those sync points, cancelling wall-clock skew.
    `clock`/`wall` are injectable for deterministic tests.
    """

    def __init__(self, run_dir: str, rank: int = 0, world: int = 1, *,
                 buffer_events: int = 2048,
                 max_file_bytes: int = 16 << 20,
                 sample_rate: float = 1.0,
                 seed: int = 0,
                 flush_interval_s: float = 0.5,
                 wire=None,
                 clock: Callable[[], float] = time.perf_counter,
                 wall: Callable[[], float] = time.time):
        import os

        self.rank = int(rank)
        self.world = int(world)
        self.sample_rate = float(sample_rate)
        self.seed = int(seed)
        self.max_file_bytes = int(max_file_bytes)
        self._clock = clock
        self._wall = wall
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=max(16, int(buffer_events)))
        self._pending: List[str] = []
        self._bytes_written = 0
        self._n_events = 0
        self._n_dropped = 0
        self._closed = False

        os.makedirs(run_dir, exist_ok=True)
        self.path = os.path.join(
            run_dir, f"{TRACE_FILE_PREFIX}{self.rank:05d}.jsonl")
        self._f = open(self.path, "a")

        skew_est_s = self._clock_sync(wire)
        meta = {"type": "trace_meta", "v": TRACE_SCHEMA_VERSION,
                "rank": self.rank, "world": self.world,
                "sync_mono_us": self._sync_mono_us,
                "sync_wall": self._sync_wall,
                "skew_est_s": skew_est_s,
                "sample_rate": self.sample_rate, "seed": self.seed}
        self._f.write(json.dumps(meta) + "\n")
        self._f.flush()

        self._stop = threading.Event()
        self._flush_interval_s = max(0.05, float(flush_interval_s))
        self._thread = threading.Thread(
            target=self._flush_loop, name="dstpu-trace-flush", daemon=True)
        self._thread.start()

    # -- clocks --------------------------------------------------------

    def now_us(self) -> int:
        return int(self._clock() * 1e6)

    def _clock_sync(self, wire) -> Optional[float]:
        """Capture the (wall, mono) pair defining this rank's timeline
        origin.  With a wire, all ranks allgather first so the capture
        happens right after a collective returns — an approximately
        simultaneous instant on every rank (within wire latency), which
        is what lets the merger cancel wall-clock skew."""
        skew_est_s = None
        if wire is not None:
            try:
                payload = json.dumps(
                    {"rank": self.rank, "wall": self._wall()}).encode()
                parts = wire.allgather_bytes(payload)
                peers = []
                for p in parts:
                    try:
                        peers.append(json.loads(p.decode()))
                    except Exception:
                        continue
                sends = [p["wall"] for p in peers if "wall" in p]
                if sends:
                    # my send-time offset from the earliest sender: a
                    # rough per-rank skew indicator for the report (the
                    # ALIGNMENT itself uses the sync instant below)
                    skew_est_s = round(
                        dict((p["rank"], p["wall"]) for p in peers)
                        .get(self.rank, min(sends)) - min(sends), 6)
            except Exception:
                pass  # tracing must never take the run down
        self._sync_wall = self._wall()
        self._sync_mono_us = self.now_us()
        return skew_est_s

    # -- sampling ------------------------------------------------------

    def sampled(self, key) -> bool:
        """Deterministic per-step / per-request gate: same seed + same
        key sequence => the same decisions on every run and rank."""
        if self.sample_rate >= 1.0:
            return True
        return _sample_hash(self.seed, key) < self.sample_rate

    # -- recording -----------------------------------------------------

    def span(self, name: str, cat: str = "train", **args) -> _SpanCtx:
        """Measure a host-side block as one complete event.  Dispatch
        walls only — never synchronizes device values."""
        return _SpanCtx(self, name, cat, args)

    def add_complete(self, name: str, cat: str = "train",
                     ts_us: Optional[int] = None, dur_us: int = 0,
                     **args) -> None:
        """An externally-measured span (e.g. a queue wait whose start
        predates the recording site)."""
        if ts_us is None:
            ts_us = self.now_us() - int(dur_us)
        self._record({"ph": "X", "name": name, "cat": cat,
                      "ts": int(ts_us), "dur": max(0, int(dur_us)),
                      **({"args": args} if args else {})})

    def instant(self, name: str, cat: str = "train", **args) -> None:
        self._record({"ph": "i", "name": name, "cat": cat,
                      "ts": self.now_us(),
                      **({"args": args} if args else {})})

    def _record(self, event: Dict[str, Any]) -> None:
        if self._closed:
            return
        with self._lock:
            self._ring.append(event)
            self._pending.append(json.dumps(event))
            self._n_events += 1

    def last_events(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """The flight recorder: a snapshot of the newest events in the
        ring (newest last).  Safe to call from the watchdog thread."""
        with self._lock:
            tail = list(self._ring)
        return tail if n is None else tail[-int(n):]

    # -- writer --------------------------------------------------------

    def _flush_loop(self) -> None:
        while not self._stop.wait(self._flush_interval_s):
            self.flush()

    def flush(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            return
        wrote = dropped = nbytes = 0
        for line in pending:
            ln = len(line) + 1
            if self._bytes_written + ln > self.max_file_bytes:
                dropped += 1
                continue
            try:
                self._f.write(line + "\n")
            except ValueError:  # closed file under teardown races
                return
            self._bytes_written += ln
            wrote += 1
            nbytes += ln
        if wrote:
            try:
                self._f.flush()
            except ValueError:
                return
            COUNTERS.add("trace.events", nbytes, calls=wrote)
        if dropped:
            self._n_dropped += dropped
            COUNTERS.add("trace.dropped", calls=dropped)

    def close(self) -> None:
        """Stop the flush thread, drain, and write the footer summary.
        Idempotent; the footer rides past the byte cap so a capped file
        still ends with its own accounting."""
        if self._closed:
            return
        self._stop.set()
        self._thread.join(timeout=10)
        self.flush()
        self._closed = True
        footer = {"type": "trace_summary", "rank": self.rank,
                  "events": self._n_events, "dropped": self._n_dropped,
                  "bytes": self._bytes_written}
        try:
            self._f.write(json.dumps(footer) + "\n")
            self._f.flush()
            self._f.close()
        except ValueError:
            pass


def read_trace_file(path: str):
    """Parse one rank's trace JSONL into ([(meta, events), ...],
    summary).  A restarted run appends a fresh meta line; events belong
    to the meta that precedes them (one segment per process lifetime,
    each with its own clock origin), so the merger aligns per
    segment."""
    segments = []
    meta, events, summary = None, [], None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except Exception:
                continue
            t = obj.get("type")
            if t == "trace_meta":
                if meta is not None:
                    segments.append((meta, events))
                meta, events = obj, []
            elif t == "trace_summary":
                summary = obj
            elif "ph" in obj:
                events.append(obj)
    if meta is not None:
        segments.append((meta, events))
    return segments, summary


class ServingSLO:
    """Sliding-window serving telemetry: p50/p99 TTFT (nearest-rank,
    the serve_bench definition), tokens/s, mean queue depth, speculative
    acceptance rate, shed count.  `tick()` (called from the serve loop)
    emits an `slo` monitor event every `emit_interval_s`; `force()`
    emits unconditionally (lane teardown).  Clock injectable — serving
    tests drive a fake clock."""

    def __init__(self, emit: Optional[Callable[[Dict[str, Any]], None]]
                 = None, window_s: float = 10.0,
                 emit_interval_s: float = 2.0,
                 clock: Callable[[], float] = time.monotonic,
                 tracer: Optional[TraceRecorder] = None):
        if window_s <= 0 or emit_interval_s <= 0:
            raise ValueError("ServingSLO: window_s and emit_interval_s "
                             "must be > 0")
        self.emit = emit
        self.window_s = float(window_s)
        self.emit_interval_s = float(emit_interval_s)
        self.clock = clock
        self.tracer = tracer
        self._ttft: collections.deque = collections.deque()
        self._tokens: collections.deque = collections.deque()
        self._queue: collections.deque = collections.deque()
        self._accept: collections.deque = collections.deque()
        self._shed: collections.deque = collections.deque()
        self._last_emit: Optional[float] = None
        self.windows_emitted = 0

    # -- observations --------------------------------------------------

    def _now(self, t: Optional[float]) -> float:
        return self.clock() if t is None else float(t)

    def observe_ttft(self, ttft_s: float, t: Optional[float] = None):
        self._ttft.append((self._now(t), float(ttft_s) * 1e3))

    def observe_tokens(self, n: int, t: Optional[float] = None):
        if n:
            self._tokens.append((self._now(t), int(n)))

    def observe_queue_depth(self, depth: int, t: Optional[float] = None):
        self._queue.append((self._now(t), int(depth)))

    def observe_accept(self, accepted: int, drafted: int,
                       t: Optional[float] = None):
        self._accept.append((self._now(t), int(accepted), int(drafted)))

    def observe_shed(self, n: int = 1, t: Optional[float] = None):
        self._shed.append((self._now(t), int(n)))

    def _trim(self, now: float) -> None:
        cutoff = now - self.window_s
        for dq in (self._ttft, self._tokens, self._queue, self._accept,
                   self._shed):
            while dq and dq[0][0] < cutoff:
                dq.popleft()

    # -- window math ---------------------------------------------------

    def snapshot(self, t: Optional[float] = None) -> Dict[str, Any]:
        now = self._now(t)
        self._trim(now)
        ttfts = sorted(ms for _, ms in self._ttft)
        toks = sum(n for _, n in self._tokens)
        # tokens/s over the span the window actually covers, not the
        # nominal width — a 2 s old lane must not read as 1/5 the rate
        tmin = min((dq[0][0] for dq in (self._tokens, self._ttft)
                    if dq), default=now)
        span = min(self.window_s, max(now - tmin, 1e-9))
        depths = [d for _, d in self._queue]
        acc = sum(a for _, a, _d in self._accept)
        drafted = sum(d for _, _a, d in self._accept)
        return {
            "window_s": self.window_s,
            "requests": len(ttfts),
            "ttft_ms": {
                "p50": round(percentile_nearest_rank(ttfts, 50), 3),
                "p99": round(percentile_nearest_rank(ttfts, 99), 3),
                "n": len(ttfts)},
            "tok_per_s": round(toks / span, 2) if toks else 0.0,
            "queue_depth_mean": (round(sum(depths) / len(depths), 2)
                                 if depths else 0.0),
            "accept_rate": (round(acc / drafted, 4) if drafted else None),
            "drafted": drafted,
            "shed": sum(n for _, n in self._shed),
        }

    # -- emission ------------------------------------------------------

    def tick(self, t: Optional[float] = None) -> Optional[Dict[str, Any]]:
        now = self._now(t)
        if self._last_emit is None:
            self._last_emit = now
            return None
        if now - self._last_emit < self.emit_interval_s:
            return None
        return self.force(now)

    def force(self, t: Optional[float] = None) -> Dict[str, Any]:
        now = self._now(t)
        snap = self.snapshot(now)
        self._last_emit = now
        self.windows_emitted += 1
        COUNTERS.add("slo.windows", calls=1)
        if self.tracer is not None:
            self.tracer.instant("slo_window", "slo",
                                p99_ttft_ms=snap["ttft_ms"]["p99"],
                                tok_per_s=snap["tok_per_s"])
        if self.emit is not None:
            self.emit(snap)
        return snap
