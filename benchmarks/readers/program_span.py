"""A statistic of the program's own spans (`monitor/tracing.py`, attached
with `engine.attach_tracing`, sampling at 1), host clock.  args: `name`
(the span), `stat`, `scale` (multiplies microseconds; 0.001 for ms)."""

from benchmarks.harness import STATS


def read(*, cell, run, trace, name: str, stat: str = "p95",
         scale: float = 1.0):
    xs = [e["dur"] for e in run.program_spans
          if e.get("name") == name and e.get("ph") == "X"]
    return None if not xs else STATS[stat](xs) * scale
