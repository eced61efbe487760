"""Device time by the scope an instruction was WRITTEN under, from inside:
the program's own map and the device's own events.

The program says which instruction of each compiled program lies under
which `jax.named_scope`: one `program_scopes` event a program among
`run.program_spans` (`program`: `jit_decode`; `paths`: the scope paths,
`attn/full_attend/kernel.grouped_attention`; `instructions`: {instruction
name: index into `paths`}).  The trace says when each instruction ran: an
`XLA Modules` event a run of a program, and inside it (by time, same
device) the `XLA Ops` events, each named by its HLO text, which begins
with the instruction's name.  Each nanosecond is counted once: where one
event lies inside another (a `while` and its body) the innermost event
that has a scope takes the time and the outer one keeps the rest.

args: `program` (`jit_decode`; none: every program), `match` (a regular
expression on the scope path; `"^$"` selects what has no stage: an
instruction the map lacks, a path under none of `STAGES`, an operation
inside no run of a mapped program), `per`: `run` — matching device time
÷ the program's runs that lie wholly in the window, times `scale`
(seconds x 1000: ms a decode step, ms a prefill chunk) — or `busy`: % of
all device busy time in the window.  None without a trace, without a
`program_scopes` event for `program` (for any, where none is given), or
where the program did not run in the window."""

import bisect
import re

from benchmarks import trace_reduce

STAGES = ("embed", "attn", "state", "ffn", "head", "sample")
_NAME = re.compile(r"^%?([\w.\-]+) = ")


def scope_maps(spans):
    """{program: {instruction: path}}; a path under none of `STAGES` is
    no path."""
    maps = {}
    for e in spans:
        if e.get("name") != "program_scopes":
            continue
        args = e["args"]
        paths = [p if p.split("/", 1)[0] in STAGES else ""
                 for p in args["paths"]]
        maps[args["program"]] = {k: paths[i]
                                 for k, i in args["instructions"].items()}
    return maps


def attribute(device, maps, window):
    """-> (runs, ns): `runs` the device's program runs wholly inside the
    window, [(start, end, program)] by start; `ns` {(index into runs or
    None, path): nanoseconds} over every `XLA Ops` event of the device,
    each nanosecond once."""
    hi = window[1]
    runs = sorted((e.start, e.end, trace_reduce.module_name(e.name))
                  for e in device.modules if e.end <= hi)
    starts = [r[0] for r in runs]
    ns, stack, t = {}, [], 0   # stack: (end, run, path) of the open events

    def credit(until):
        if stack and until > t:
            key = stack[-1][1:]
            ns[key] = ns.get(key, 0) + until - t

    for e in sorted(device.ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= e.start:
            credit(stack[-1][0])
            t = max(t, stack.pop()[0])
        credit(e.start)
        t = e.start
        i = bisect.bisect_right(starts, e.start) - 1
        run = i if i >= 0 and e.start < runs[i][1] else None
        m = _NAME.match(e.name)
        path = maps.get(runs[run][2], {}).get(m.group(1), "") \
            if m and run is not None else ""
        end = e.end
        if stack:    # an inner event without a path is its outer one's
            end = min(end, stack[-1][0])
            if not path:
                run, path = stack[-1][1:]
        stack.append((end, run, path))
    while stack:
        credit(stack[-1][0])
        t = max(t, stack.pop()[0])
    return runs, ns


def read(*, cell, run, trace, match: str, program=None, per: str = "run",
         scale: float = 1000.0):
    if trace is None:
        return None
    maps = scope_maps(run.program_spans)
    if not maps or (program is not None and program not in maps):
        return None
    rx = re.compile(match)
    num = den = 0
    for d in trace.devices.values():
        runs, ns = attribute(d, maps, trace.window)
        mine = {i for i, r in enumerate(runs) if r[2] == program}
        num += sum(v for (i, path), v in ns.items() if rx.search(path)
                   and (program is None or i in mine))
        den += trace_reduce.total(d.busy) if per == "busy" else len(mine)
    if not den:
        return None
    return 100.0 * num / den if per == "busy" else num / den / 1e9 * scale
