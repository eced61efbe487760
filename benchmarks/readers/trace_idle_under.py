"""The device's idle time laid against what the program's host thread said
it was doing: the first device's idle gaps of 20 us or more, as
`trace_reduce` finds them, INTERSECTED with the union of the host events
whose name matches — exact overlap in nanoseconds, not "covers most of the
gap", so a gap that two phases share is split between them and a nested
phase is counted once.  args: `match` (a regular expression on the host
event's name), `present` (optional: a wider expression; the program counts
as emitting its phases when any host event matches it, so a phase that
simply did not occur in the window reads 0 and not nothing; default
`match`), `per`: `window` (% of the traced window) or `step` (ms a traced
step; the runner says how many).  None without a trace, or where no host
event matches `present`: the program has no such annotation."""

import re

from benchmarks import trace_reduce


def idle_under(trace, match: str) -> int:
    """ns of the first device's idle gaps that host events matching
    `match` cover."""
    rx = re.compile(match)
    lo, hi = trace.window
    first = trace.devices[min(trace.devices)]
    idle = [g for g in trace_reduce.gaps(first.busy, lo, hi)
            if g[1] - g[0] >= trace_reduce.MIN_GAP_NS]
    said = trace_reduce.union((max(e.start, lo), min(e.end, hi))
                              for e in trace.host if rx.search(e.name))
    return trace_reduce.total(idle) - trace_reduce.total(
        trace_reduce.subtract(idle, said))


def read(*, cell, run, trace, match: str, present=None, per: str = "window"):
    if trace is None:
        return None
    there = re.compile(present or match)
    if not any(there.search(e.name) for e in trace.host):
        return None
    ns = idle_under(trace, match)
    if per == "window":
        return 100.0 * ns / 1e9 / trace.window_s
    steps = run.shapes.get("steps_traced")
    return ns / 1e6 / steps if steps else None
