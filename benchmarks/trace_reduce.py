"""From a profiler trace (`.xplane.pb`) to the numbers the readers read.

What the TPU v5e's trace looks like (recorded by tests/data/record_trace.py,
read by hand before this was written): one plane `/device:TPU:<n>` per chip
with the lines `XLA Modules` (one event per executed program, named
`jit_<function>(<fingerprint>)`), `XLA Ops` (one event per HLO operation,
named by its whole HLO text) and `Async XLA Ops` (copies and collectives in
flight); and one plane `/host:CPU` with a line per thread, on which
`jax.profiler.TraceAnnotation`s appear under their own names beside JAX's
own (`PjitFunction(f)`, `np.asarray(jax.Array)`, `shard_args`).  Host and
device events share one clock to within about half a millisecond.

The window is the `bench.window` annotation the runner puts around the
traced steady part; device events are clipped to it.

    busy      union of the `XLA Ops` intervals on a device
    idle gap  a stretch of the window with no `XLA Ops` event, named by the
              benchmark's own annotation (`bench.*`) that covers most of it,
              else by JAX's own host event there, marked `unannotated:`
    op time   device time summed by a short name: opcode, fusion kind or
              custom-call target, and result shapes without layouts
    collective  all-reduce, all-gather, reduce-scatter, all-to-all and
              collective-permute events of both op lines; exposed is the
              part of their union in which no other `XLA Ops` event runs
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re

WINDOW = "bench.window"
OWN = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
MIN_GAP_NS = 20_000  # shorter stretches between two operations are not gaps

_HLO = re.compile(r"^%?(?P<lhs>[\w.\-]+) = (?P<shape>.*?) "
                  r"(?P<op>[a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


# --------------------------------------------------------------------------
# interval arithmetic, in whole nanoseconds
# --------------------------------------------------------------------------


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(disjoint) -> int:
    return sum(b - a for a, b in disjoint)


def subtract(disjoint_a, disjoint_b):
    """The part of A (disjoint, sorted) that no interval of B covers."""
    out, j = [], 0
    for a, b in disjoint_a:
        cur = a
        while j < len(disjoint_b) and disjoint_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(disjoint_b) and disjoint_b[k][0] < b:
            if disjoint_b[k][0] > cur:
                out.append((cur, disjoint_b[k][0]))
            cur = max(cur, disjoint_b[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def gaps(disjoint, lo, hi):
    return subtract([(lo, hi)], disjoint)


# --------------------------------------------------------------------------


def short_name(hlo: str) -> str:
    """`fusion:kCustom bf16[1024,256]` from an `XLA Ops` event's HLO text."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:100]
    op = m.group("op")
    kind = re.search(r"kind=(\w+)", hlo) if op == "fusion" else \
        re.search(r'custom_call_target="([^"]+)"', hlo) \
        if op == "custom-call" else None
    shape = _LAYOUT.sub("", m.group("shape"))
    return (f"{op}:{kind.group(1)}" if kind else op) + " " + shape[:90]


def module_name(event_name: str) -> str:
    """`jit_full_step` from `jit_full_step(9147376617240341932)`."""
    return event_name.split("(", 1)[0]


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    ops: list          # Events of `XLA Ops`, clipped to the window
    async_ops: list    # Events of `Async XLA Ops`, clipped
    modules: list      # Events of `XLA Modules` that start in the window
    busy: list         # disjoint intervals

    def collective(self):
        return union((e.start, e.end) for e in self.ops + self.async_ops
                     if COLLECTIVE.search(e.name))

    def compute(self):
        return union((e.start, e.end) for e in self.ops
                     if not COLLECTIVE.search(e.name))


@dataclasses.dataclass
class Trace:
    window: tuple      # (lo, hi) in ns on the trace's clock
    devices: dict      # device index -> Device
    host: list         # Events of the host plane, any thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices."""
        return sum(total(d.busy) for d in self.devices.values()) \
            / len(self.devices) / 1e9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_seconds(self, by=short_name):
        """Device time by name, summed over ops and averaged over devices,
        longest first."""
        acc = {}
        for d in self.devices.values():
            for e in d.ops:
                k = by(e.name)
                acc[k] = acc.get(k, 0) + e.dur
        n = len(self.devices)
        return sorted(((k, v / n / 1e9) for k, v in acc.items()),
                      key=lambda kv: -kv[1])

    def module_durations(self, name: str):
        """Device durations, in seconds, of every run of program `name`
        (`jit_decode`) that started in the window, over all devices."""
        return [e.dur / 1e9 for d in self.devices.values()
                for e in d.modules if module_name(e.name) == name]

    def matching_op_seconds(self, pattern: str) -> float:
        """Device seconds of `XLA Ops` events whose HLO text matches,
        averaged over devices."""
        rx = re.compile(pattern)
        return sum(e.dur for d in self.devices.values() for e in d.ops
                   if rx.search(e.name)) / len(self.devices) / 1e9

    def matching_op_count(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(1 for d in self.devices.values() for e in d.ops
                   if rx.search(e.name)) / len(self.devices)

    def collective_s(self) -> float:
        return sum(total(d.collective()) for d in self.devices.values()) \
            / len(self.devices) / 1e9

    def collective_exposed_s(self) -> float:
        return sum(total(subtract(d.collective(), d.compute()))
                   for d in self.devices.values()) / len(self.devices) / 1e9

    def idle_gaps(self):
        """[(name, seconds)] of the first device's idle time, by what the
        host was doing, longest first."""
        first = self.devices[min(self.devices)]
        own = _ByStart(e for e in self.host
                       if e.name.startswith(OWN) and e.name != WINDOW)
        other = _ByStart(e for e in self.host if not e.name.startswith(OWN))
        acc = {}
        for a, b in gaps(first.busy, *self.window):
            if b - a < MIN_GAP_NS:
                continue
            name = own.covering(a, b)
            if name is None:
                name = "unannotated:" + (other.covering(a, b) or "nothing")
            acc[name] = acc.get(name, 0) + (b - a)
        return sorted(((k, v / 1e9) for k, v in acc.items()),
                      key=lambda kv: -kv[1])

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.op_seconds()[:10]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:10]]}


class _ByStart:
    """Host events sorted by start, with the running maximum of their ends,
    so a query looks only at events that can still reach its interval."""

    def __init__(self, events):
        self.events = sorted(events, key=lambda e: e.start)
        self.starts = [e.start for e in self.events]
        self.reach, far = [], 0
        for e in self.events:
            far = max(far, e.end)
            self.reach.append(far)

    def covering(self, a, b):
        """The name of the event that covers most of [a, b); of two that
        cover as much, the shorter (the inner one of a nest)."""
        best, best_key = None, (0, 0)
        i = bisect.bisect_left(self.starts, b) - 1
        while i >= 0 and self.reach[i] > a:
            e = self.events[i]
            ov = min(e.end, b) - max(e.start, a)
            if ov > 0 and (ov, -e.dur) > best_key:
                best, best_key = e.name, (ov, -e.dur)
            i -= 1
        return best


# --------------------------------------------------------------------------


def load(path: str):
    """jax.profiler.ProfileData of an `.xplane.pb` or `.xplane.pb.gz`."""
    import jax

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(path)


def _events(line):
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce(data, window=None) -> Trace:
    """`window`: (lo, hi) ns, else the `bench.window` annotation, else the
    span of all device operations and programs."""
    host, raw = [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: _events(ln) for ln in plane.lines
                     if ln.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE):
                raw[int(m.group(1))] = lines
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.extend(_events(ln))
    if not raw:
        raise ValueError("the trace has no device plane with XLA Ops: no "
                         "operation ran on a device while it was recorded")
    if window is None:
        marks = [e for e in host if e.name == WINDOW]
        if marks:
            window = (min(e.start for e in marks), max(e.end for e in marks))
        else:
            ops = [e for ln in raw.values()
                   for e in ln[OPS_LINE] + ln.get(MODULES_LINE, [])]
            window = (min(e.start for e in ops), max(e.end for e in ops))
    lo, hi = window

    def clipped(events):
        return [Event(e.name, max(e.start, lo), min(e.end, hi))
                for e in events if min(e.end, hi) > max(e.start, lo)]

    devices = {}
    for idx, lines in raw.items():
        ops = clipped(lines[OPS_LINE])
        devices[idx] = Device(
            ops=ops, async_ops=clipped(lines.get(ASYNC_LINE, [])),
            modules=[e for e in lines.get(MODULES_LINE, [])
                     if lo <= e.start < hi],
            busy=union((e.start, e.end) for e in ops))
    return Trace(window=(lo, hi), devices=devices,
                 host=[e for e in host if e.end > lo and e.start < hi])


def reduce_file(path: str, window=None) -> Trace:
    return reduce(load(path), window)
