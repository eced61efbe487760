"""One run of benchmarks/run.py for PR 59's chip calls, kept as one JSON
line.  This process never imports JAX: the child holds the chip.

    python bench_artifacts/pr59/run_one.py <label> <tree> <cell> <seed> <trace>

`tree`: `change` (the checkout this file lies in), `final` (`git archive
$(git write-tree)` unpacked under .scratch/final: the files git would
commit and nothing else) or `parent` (the parent commit unpacked under
.scratch/parent with this PR's BENCHMARK.json and benchmarks/ laid over
it, as the driver does; mkparent.sh).  After a traced run of the change,
stage_sums.py reads the files the run left.
The line goes to chiprun_out/pr59/<label>.jsonl: the result line, the
comparison's note, the run's wall seconds and the stage sums.
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
label, tree, cell, seed, trace = sys.argv[1:6]
where = ROOT if tree == "change" else os.path.join(ROOT, ".scratch", tree)
out_dir = os.path.join(ROOT, "chiprun_out", "pr59")
os.makedirs(out_dir, exist_ok=True)
t0 = time.time()
p = subprocess.run(
    [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", seed,
     "--seconds", "50", "--trace", trace], cwd=where, capture_output=True,
    text=True)
wall = time.time() - t0
with open(os.path.join(out_dir, label + ".err"), "a") as f:
    f.write(f"== {tree} {cell} {seed} trace={trace} rc={p.returncode}\n")
    f.write(p.stderr[-6000:])
lines = p.stdout.strip().splitlines()
notes = [json.loads(ln[2:]) for ln in lines if ln.startswith("# {")]
record = {"pr": 59, "label": label, "tree": tree, "workload": cell,
          "seed": int(seed), "trace": int(trace), "rc": p.returncode,
          "wall_s": round(wall, 1),
          "result": json.loads(lines[-1]) if lines and
          lines[-1].startswith("{") else None,
          "notes": notes[1:-1]}
if tree != "parent" and trace == "1" and p.returncode == 0:
    s = subprocess.run(
        [sys.executable, "bench_artifacts/pr59/stage_sums.py", cell],
        cwd=where, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if s.returncode == 0:
        record["stage_sums"] = json.loads(s.stdout.strip().splitlines()[-1])
        print(s.stderr[-3000:])
    else:
        record["stage_sums_error"] = s.stderr[-2000:]
with open(os.path.join(out_dir, label + ".jsonl"), "a") as f:
    f.write(json.dumps(record) + "\n")
r = record["result"] or {}
print(json.dumps({k: record[k] for k in ("tree", "workload", "seed", "trace",
                                         "rc", "wall_s")}),
      json.dumps({"correct": r.get("correct"), "failed": r.get("failed"),
                  "metrics": {k: round(v["value"], 4) for k, v in
                              (r.get("metrics") or {}).items()}})[:3000])
if record.get("stage_sums_error"):
    print(record["stage_sums_error"])
