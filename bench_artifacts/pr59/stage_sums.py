"""After `benchmarks/run.py --trace 1`: the stages of each program set
beside its runs' device time, from the files the run left in
`.bench_tmp/` (the profile and the recorder's file).

    python bench_artifacts/pr59/stage_sums.py <cell> [<out.jsonl>]

One JSON line: for each program its runs in the window, their mean
device time, ms a run by stage, the share of a run the stages and what
has no stage sum to (the acceptance criterion: within 2 % of 100), the
scopes two levels down, every `kernel.<op>` / `oracle.<op>` of the
registry, the compiler's own data movement (`xla.<opcode>`, counted with
its consumer), the instructions no stage owns (largest first),
and what `attach_tracing` cost (the `seconds` of each `program_scopes`
event, its size in the recorder's file).  Uses `monitor/tracing.py`'s
join, not the benchmark's reader: the two are held equal by a test.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from deepspeed_tpu.monitor import tracing
    from deepspeed_tpu.serving.programs import STAGES

    cell = sys.argv[1]
    tmp = os.path.join(ROOT, ".bench_tmp")
    segments, _ = tracing.read_trace_file(
        os.path.join(tmp, "spans", "trace.rank00000.jsonl"))
    events = segments[-1][1]
    path = glob.glob(os.path.join(tmp, "trace", "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    profile = tracing.load_profile(path)
    marks = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
             for plane in profile.planes if plane.name == "/host:CPU"
             for ln in plane.lines for e in ln.events
             if e.name == "bench.window"]
    window = (min(a for a, _ in marks), max(b for _, b in marks))
    times = tracing.device_scope_times(profile, events, window)
    out = {"cell": cell, "window_s": (window[1] - window[0]) / 1e9,
           "recorder_events": len(events),
           "attach": {e["args"]["program"]: {
               "seconds": e["args"]["seconds"],
               "instructions": len(e["args"]["instructions"]),
               "paths": len(e["args"]["paths"]),
               "event_bytes": len(json.dumps(e))}
               for e in events if e["name"] == "program_scopes"},
           "programs": {}}
    for program, got in times.items():
        if not got["runs"]:
            continue
        run = got["run_ns"]
        stages, second, kernels, moved = {}, {}, {}, 0.0
        for p, ns in got["paths"].items():
            stage = tracing.stage_of(p, STAGES) or "(none)"
            stages[stage] = stages.get(stage, 0) + ns / 1e6
            if stage != "(none)":
                key = "/".join(p.split("/")[:2])
                second[key] = second.get(key, 0) + ns / 1e6
            parts = p.split("/")
            moved += ns / 1e6 if parts[-1].startswith("xla.") else 0.0
            for i, c in enumerate(parts):   # up to the registry's wrap
                if c.startswith(("kernel.", "oracle.")):
                    key = "/".join(parts[:i + 1])
                    kernels[key] = kernels.get(key, 0) + ns / 1e6
        out["programs"][program] = {
            "runs": got["runs"], "run_ms": run / 1e6, "stage_ms": stages,
            "sum_over_run_pct": 100 * sum(got["paths"].values()) / run,
            "second_level_ms": dict(sorted(second.items(),
                                           key=lambda kv: -kv[1])[:16]),
            "registry_ms": dict(sorted(kernels.items(),
                                       key=lambda kv: -kv[1])),
            "compiler_moved_ms": moved,
            "unscoped_ms": {k: v / 1e6 for k, v in sorted(
                got["unscoped"].items(), key=lambda kv: -kv[1])[:8]}}
    line = json.dumps(out)
    print(line)
    print("\n".join(tracing.scope_table(times, STAGES)), file=sys.stderr)
    if len(sys.argv) > 2:
        os.makedirs(os.path.dirname(os.path.abspath(sys.argv[2])),
                    exist_ok=True)
        with open(sys.argv[2], "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
