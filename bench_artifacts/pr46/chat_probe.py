"""`gpt2-xl.serve.chat` through its own runner in the checkout `TREE`
(default: this one), printing beside the end-to-end numbers WHICH
requests make the first-token tail: the six largest times from due to
first token with each request's place in the deal and its prompt length.
A builder's script (PR 46): call D read the tail 49.6 ms on the parent
and 58.2 on the change, on programs that lower to the same StableHLO.

    TREE=.scratch/parent python3 bench_artifacts/pr46/chat_probe.py --seed 1
"""
import argparse, json, os, sys
ROOT = os.path.abspath(os.environ.get("TREE") or os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)


def main():
    from benchmarks import run
    from benchmarks.runners import serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2-xl.serve.chat")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--freeze", type=int, default=0,
                    help="1: gc.collect() and gc.freeze() as the worker "
                    "starts, after warm-up: is the ~100 ms stall a full "
                    "garbage collection over the traced programs?")
    args = ap.parse_args(None, argparse.Namespace(trace=0))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell = run.build_cell(args, benchmark)
    seen = {}
    orig = serve.due_latencies_ms

    def keep(due, first, window):
        seen["ttft"] = orig(due, first, window)
        return seen["ttft"]

    serve.due_latencies_ms = keep
    if args.freeze:
        import gc

        from deepspeed_tpu.serving import ServeWorker

        start = ServeWorker.start

        def frozen_start(self):
            gc.collect()
            gc.freeze()
            start(self)

        ServeWorker.start = frozen_start
    result = serve.run(cell)
    lengths = [len(p) for _, p, _ in cell.generator.timeline(
        cell.traffic, seed=cell.seed, seconds=cell.seconds,
        config=cell.config, family=cell.family)]
    order = sorted(range(len(seen["ttft"])), key=lambda i: -seen["ttft"][i])
    load = result.notes[0]
    print(json.dumps({
        "tree": ROOT, "seed": args.seed, "freeze": args.freeze,
        "correct": result.correct,
        **{k: round(v, 3) for k, v in result.end_to_end.items()},
        "tail": [[i, lengths[i], round(seen["ttft"][i], 1)]
                 for i in order[:6]],
        "generator_late_ms_max": round(load["generator_late_ms_max"], 1),
        "observer_late_ms_max": round(load["observer_late_ms_max"], 1),
        "engine_steps": load["engine_steps"]}), flush=True)


if __name__ == "__main__":
    main()
