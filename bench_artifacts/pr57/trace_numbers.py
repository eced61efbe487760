"""What PERF.md quotes from a traced run beyond the result line (my chip
runs, PR 57): the programs' device times and counts in the traced
stretch, the Mosaic kernels' seconds by name, and the top operations.
Run after `benchmarks/run.py --trace 1` in the same call, while
`.bench_tmp/trace` still holds the run's `.xplane.pb`.

    python3 bench_artifacts/pr57/trace_numbers.py
"""
import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from benchmarks import trace_reduce

    path = glob.glob(os.path.join(ROOT, ".bench_tmp", "trace", "plugins",
                                  "profile", "*", "*.xplane.pb"))[0]
    trace = trace_reduce.reduce_file(path)
    out = {"window_s": trace.window_s, "busy_s": trace.busy_s}
    for program in ("jit_decode", "jit_prefill"):
        times = sorted(trace.module_durations(program))
        if times:
            out[program] = {"runs": len(times),
                            "median_ms": 1e3 * statistics.median(times),
                            "total_s": sum(times)}
    for kernel in ("gdn_step_live", "paged_attention_walk",
                   "paged_attention_prefill_walk", "touched_experts",
                   "ragged"):
        out[kernel] = {"seconds": trace.matching_op_seconds(kernel),
                       "events": trace.matching_op_count(kernel)}
    out["device_ops"] = trace.op_seconds()[:24]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
