"""A statistic of the durations of the program's own host phases in the
trace: the `jax.profiler.TraceAnnotation`s an engine's thread opens around
what it is doing (`deepspeed_tpu/monitor/tracing.py::phase`), which lie on
the host plane of the same trace as the device's operations.  Events that
begin inside the `bench.window` count.  args: `name` (the annotation),
`stat` (median, p95, mean, max, sum), `scale` (multiplies seconds; 1000
for ms).  None without a trace or where the program emits no such phase."""

from benchmarks.harness import STATS


def read(*, cell, run, trace, name: str, stat: str = "median",
         scale: float = 1.0):
    if trace is None:
        return None
    lo, hi = trace.window
    xs = [e.dur / 1e9 for e in trace.host
          if e.name == name and lo <= e.start < hi]
    return None if not xs else STATS[stat](xs) * scale
