"""The `# {...}` note lines of a call's `.full` file, a few keys a run:
`python notes.py <file.full> ...`."""
import json
import sys

KEEP = ("ttft_ms_median", "itl_ms_median", "itl_ms_max",
        "observer_late_ms_max", "drained_s", "backlog_at_end_of_sending",
        "backlog_at_middle", "compiles_in_window", "engine_steps",
        "peak_blocks_in_use", "worst_gap_to_top_logit", "top1_agreement",
        "memory_peak_bytes", "busy_s", "window_s", "mfu_pct")
for path in sys.argv[1:]:
    run = {}
    for line in open(path):
        if line.startswith("# {"):
            d = json.loads(line[2:])
            if "workload" in d:
                if run:
                    print(json.dumps(run))
                run = {"cell": d["workload"].split(".")[-1],
                       "seed": d["seed"], "trace": d["trace"]}
            run.update({k: (round(v, 4) if isinstance(v, float) else v)
                        for k, v in d.items() if k in KEEP})
    print(json.dumps(run))
