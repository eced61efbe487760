"""Device time from the trace.  args: either `program` (a jitted function's
name, e.g. `jit_decode`: device durations of its runs on `XLA Modules`) or
`op` (a regular expression on an `XLA Ops` event's HLO text: their summed
device time, per traced step where the runner says how many); `stat` for
`program` (median, p95, mean), `scale` (multiplies seconds)."""

from benchmarks.harness import STATS


def read(*, cell, run, trace, program=None, op=None, stat: str = "median",
         scale: float = 1.0):
    if trace is None:
        return None
    if program is not None:
        xs = trace.module_durations(program)
        return None if not xs else STATS[stat](xs) * scale
    if not trace.matching_op_count(op):
        return None
    steps = run.shapes.get("steps_traced") or 1
    return trace.matching_op_seconds(op) / steps * scale
