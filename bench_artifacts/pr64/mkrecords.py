"""Builds benchmarks/records/pr64.jsonl from the `call_<X>.out` and
`.check` files PR 64's chip calls left here (PR 62's script): one line a
run of benchmarks/run.py — side, cell, seed, trace, the comparison's line
and the result line."""
import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CALLS = json.load(open(os.path.join(HERE, "calls.json")))
lines = []
for path in sorted(glob.glob(os.path.join(HERE, "call_*.out"))):
    label = os.path.basename(path)[:-4]
    checks = {}
    if os.path.exists(path[:-4] + ".check"):
        for ln in open(path[:-4] + ".check"):
            side, cell, seed, trace, js = ln.split(" ", 4)
            checks[side, cell, seed, trace] = json.loads(js.lstrip("# "))
    for ln in open(path):
        side, cell, seed, trace, js = ln.split(" ", 4)
        lines.append({"pr": 64, "call": CALLS.get(label, ""), "label": label,
                      "tree": side, "workload": cell,
                      "seed": int(seed.split("=")[1]),
                      "trace": int(trace.split("=")[1]),
                      "check": checks.get((side, cell, seed, trace)),
                      "result": json.loads(js)})
with open(os.path.join(HERE, "..", "..", "benchmarks", "records",
                       "pr64.jsonl"), "w") as f:
    for line in lines:
        f.write(json.dumps(line) + "\n")
print(len(lines), "lines")
