"""The new cell's own runner at several rates (and, with --serve, other
engine settings) in one process: `benchmarks/sweep.py`'s loop and rule,
with the check cut to `--check` requests a rate so that a sweep costs
less, and what the first run needs to see besides: how often a generated
token repeats the one before it (seeded weights under a tied head), the
counters of the state-space layers, the fullest device.  A builder's
script (PR 46), run on the chip:

    python3 bench_artifacts/pr46/probe.py --rates 3,5,7,9 --seconds 50
"""
import argparse, gc, json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from benchmarks import run
    from benchmarks.harness import plugin

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="granite-4.0-h-micro.serve.chatrate")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=2190000146)
    ap.add_argument("--check", type=int, default=4)
    ap.add_argument("--serve", default="{}")
    args = ap.parse_args(None, argparse.Namespace(trace=0))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell = run.build_cell(args, benchmark)
    cell.workload = dict(
        cell.workload,
        serve=dict(cell.workload["serve"], **json.loads(args.serve)),
        check=dict(cell.workload["check"], requests=args.check))
    runner = plugin("runners", cell.workload["runner"])
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_rps=rate)
        result = runner.run(cell)
        load = result.notes[0]
        c = result.counters
        steps = c.get("serve.decode_steps", {"calls": 0, "bytes": 0})
        print(json.dumps({
            "rate_rps": rate, "serve": cell.workload["serve"],
            "requests": result.attempted, "failed": result.failed,
            "backlog_at_middle": load["backlog_at_middle"],
            "backlog_at_end_of_sending": load["backlog_at_end_of_sending"],
            "drained_s": load["drained_s"],
            "ttft_ms_median": load["ttft_ms_median"],
            "itl_ms_median": load["itl_ms_median"],
            **{k: v for k, v in result.end_to_end.items()},
            "decode_steps": steps["calls"],
            "batch_mean": steps["bytes"] / max(steps["calls"], 1),
            "prefill_chunks": c.get("serve.prefill_chunks"),
            "state_resets": c.get("serve.ssm.state_resets"),
            "memory_peak_gb": result.memory_peak_bytes / 1e9,
            "generator_late_ms_max": load["generator_late_ms_max"],
            "compiles_in_window": load["compiles_in_window"],
            "correct": result.correct, "check": result.notes[1]}),
            flush=True)
        del result
        gc.collect()


if __name__ == "__main__":
    main()
