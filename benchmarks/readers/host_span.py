"""A statistic of the benchmark's own host spans (`harness.Spans`), by the
host's clock.  args: `span` (its name), `stat` (median, p95, mean, max,
sum), `scale` (multiplies seconds; 1000 for ms)."""

from benchmarks.harness import STATS


def read(*, cell, run, trace, span: str, stat: str = "median",
         scale: float = 1.0):
    xs = run.host_spans.get(span)
    return None if not xs else STATS[stat](xs) * scale
