"""What run.py, the runners and the readers share: the cell as a run sees
it, what a runner hands back, host spans on the profiler's clock, and the
arithmetic of percentiles and due-time latency.  No cell, model or metric
is named here."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib
import json
import math
import os
import shutil
import sys
import threading
import time
import traceback
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    """A data file under benchmarks/."""
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, named by a data file."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def peak_bytes(devices) -> int:
    """`peak_bytes_in_use` of the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclasses.dataclass
class Cell:
    """Everything a runner and the readers need to know about one run."""

    name: str
    chips: int
    seed: int
    seconds: float
    trace: bool
    workload: dict          # workloads/<cell>.json
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    family: object          # models/<family>.py
    generator: object       # traffic/<generator>.py
    peaks: dict             # this device kind's row of peaks.json
    devices: list
    scratch: str            # a directory inside the checkout, git-ignored
    t_start: float          # perf_counter at process start
    compiles: list          # seconds of every backend compile so far


@dataclasses.dataclass
class RunResult:
    """What a runner hands back.  `end_to_end` holds every end-to-end
    value the runner can compute, by metric name; run.py prints those
    BENCHMARK.json lists for the cell.  The rest is what readers read."""

    end_to_end: dict
    correct: bool
    attempted: int
    failed: int
    notes: list                                   # dicts, printed as `# …`
    memory_peak_bytes: int                        # as the window closed
    host_spans: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    program_spans: list = dataclasses.field(default_factory=list)
    shapes: dict = dataclasses.field(default_factory=dict)
    trace_path: Optional[str] = None              # the .xplane.pb


class Spans:
    """Host spans of the benchmark's own calls: a duration by the host's
    clock for the `host_span` reader, and a `TraceAnnotation` of the same
    name so the trace reduction can say what the host was doing in a gap."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(
                    time.perf_counter() - t0)


def start_trace(path: str) -> None:
    """Start the profiler; the trace goes under `path`, emptied first."""
    import jax

    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def stop_trace(path: str) -> str:
    """Stop the profiler -> the `.xplane.pb` it wrote."""
    import jax

    jax.profiler.stop_trace()
    return glob.glob(os.path.join(path, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]


class GcPauses:
    """Times the interpreter's garbage collections while it is active: a
    full collection over a large traced program's objects can stop the
    host for seconds, and a step that waits on the host then shows it."""

    def __init__(self):
        self.pauses = []  # (generation, seconds)
        self._t0 = 0.0

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)

    def over(self, seconds: float):
        """[[generation, ms]] of the pauses longer than `seconds`."""
        return [[g, round(1e3 * s, 1)] for g, s in self.pauses
                if s > seconds]


def _machine_cpu_s():
    """(busy, iowait, steal) seconds of all cores since boot, from the
    first line of /proc/stat; zeros where there is none."""
    try:
        with open("/proc/stat") as f:
            v = [int(x) / os.sysconf("SC_CLK_TCK")
                 for x in f.readline().split()[1:9]]
    except (OSError, ValueError):
        return 0.0, 0.0, 0.0
    user, nice, system, _idle, iowait, irq, softirq, steal = v
    return user + nice + system + irq + softirq, iowait, steal


class StallWatch:
    """Says what the host was doing when a step stood still.  A thread
    wakes every `tick` seconds while the window is open and keeps: how
    late it woke (seconds late means the whole process, or the machine,
    was not running); the CPU seconds of this process and of the machine
    (/proc/stat: busy, waiting for I/O, stolen by the hypervisor); and,
    once the main thread has not called `beat` for `after` seconds, where
    its Python stack stands.  `between(a, b)` sums that up for one
    interval of the host's clock."""

    def __init__(self, tick: float = 0.05, after: float = 1.0):
        self.tick, self.after = tick, after
        self.samples = []   # (t, late_s, process_cpu_s, busy, iowait, steal)
        self.stacks = []    # (t, [file:line function, ...])
        self._main = threading.get_ident()
        self._beat_at = time.perf_counter()
        self._beats = self._dumped = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def beat(self) -> None:
        self._beat_at = time.perf_counter()
        self._beats += 1

    def _run(self):
        last = time.perf_counter()
        while not self._stop.wait(self.tick):
            now = time.perf_counter()
            self.samples.append((now, now - last - self.tick,
                                 time.process_time(), *_machine_cpu_s()))
            last = now
            if now - self._beat_at > self.after \
                    and self._dumped != self._beats:
                self._dumped = self._beats
                frame = sys._current_frames().get(self._main)
                self.stacks.append((now, [
                    f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
                    for f in traceback.extract_stack(frame)[-10:]]))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def between(self, a: float, b: float) -> dict:
        """What the samples say about the host between instants a and b."""
        inside = [s for s in self.samples if a <= s[0] <= b]
        out = {"watch_ticks": len(inside)}
        if len(inside) >= 2:
            first, last = inside[0], inside[-1]
            out.update(
                watch_late_ms_max=round(1e3 * max(s[1] for s in inside), 1),
                process_cpu_s=round(last[2] - first[2], 3),
                machine_busy_s=round(last[3] - first[3], 3),
                machine_iowait_s=round(last[4] - first[4], 3),
                machine_steal_s=round(last[5] - first[5], 3))
        out["main_thread_at"] = [st for t, st in self.stacks if a <= t <= b]
        return out


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def numpy_seed(seed: int, stream: int = 0) -> int:
    """A seed numpy's RandomState takes (below 2**32), one per stream."""
    return (int(seed) * 1000003 + stream * 7919 + 12345) % (2 ** 32)


def percentile(xs, q: float):
    """Nearest-rank percentile (copied from tools/serve_bench.py): the
    smallest sample with at least q% of the samples at or below it, so it
    is always a latency some request saw.  None for no samples."""
    if not xs:
        return None
    xs = sorted(xs)
    idx = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return xs[min(idx, len(xs) - 1)]


def median(xs):
    return percentile(xs, 50)


STATS = {"median": median, "p95": lambda xs: percentile(xs, 95),
         "mean": lambda xs: sum(xs) / len(xs) if xs else None,
         "max": lambda xs: max(xs) if xs else None,
         "sum": lambda xs: sum(xs) if xs else None}


def due_latencies_ms(due, first_token, window_s: float):
    """Time to first token from the instant each request was DUE, in ms.
    `first_token[i]` is None for a request that failed, was shed or did
    not finish: it counts as the window's length."""
    return [(window_s if t is None else t - d) * 1000.0
            for d, t in zip(due, first_token)]


def token_gaps_ms(token_times):
    """Gaps between consecutive output tokens, all requests pooled."""
    return [(b - a) * 1000.0 for ts in token_times
            for a, b in zip(ts, ts[1:])]
