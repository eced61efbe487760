"""Finds a serving cell's knee, once, when the cell is defined.

    python3 benchmarks/sweep.py --workload <cell> --rates 3,4.5,6,8,10 --seconds 60

Runs the cell's own runner at each rate in turn, in this one process, and
prints one line a rate: what was offered and completed, the tails, and the
backlog (requests submitted and not finished) at the middle and at the end
of sending.  The knee is the highest rate at which the backlog at the end
is no larger than at the middle; the cell's traffic file then takes 0.8 of
it (or 1.25 of it for a cell above capacity) as a number.  A benchmark run
never searches: this is for the PR that defines or re-finds a rate, and its
table goes into PERF.md.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmarks import run
    from benchmarks.harness import plugin

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    args = ap.parse_args(argv, argparse.Namespace(seed=1, trace=0))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    try:
        cell = run.build_cell(args, benchmark)
    except run.Refused as e:
        print(f"benchmarks/sweep.py: refused: {e}", file=sys.stderr)
        return 2
    runner = plugin("runners", cell.workload["runner"])
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_rps=rate)
        result = runner.run(cell)
        load = result.notes[0]
        print(json.dumps({
            "rate_rps": rate, "seconds": args.seconds,
            "serve": cell.workload["serve"],
            "requests": result.attempted, "failed": result.failed,
            "backlog_at_middle": load["backlog_at_middle"],
            "backlog_at_end_of_sending": load["backlog_at_end_of_sending"],
            "drained_s": load["drained_s"],
            "ttft_ms_median": load["ttft_ms_median"],
            "itl_ms_median": load["itl_ms_median"],
            **{k: v for k, v in result.end_to_end.items() if k != "setup_s"},
            "generator_late_ms_max": load["generator_late_ms_max"],
            "correct": result.correct, "check": result.notes[1]}),
            flush=True)
        del result
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
