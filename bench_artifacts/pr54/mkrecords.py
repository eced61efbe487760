"""Builds benchmarks/records/pr54.jsonl from the outputs PR 54's chip
calls left in chiprun_out/pr54/ (copied to bench_artifacts/pr54/out/):
one line a run of benchmarks/run.py (its notes and result line), a
sweep's or a sabotage script's own lines as they are, and the StableHLO
hashes of the three accepted cells at parent and change."""
import glob, json, os, re, sys
HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "out")
CALLS = json.load(open(os.path.join(HERE, "calls.json")))


def runs_of(path):
    """Every run.py run in a file: (notes, result)."""
    notes, out = [], []
    for line in open(path, errors="replace"):
        line = line.strip()
        if line.startswith("# {"):
            notes.append(json.loads(line[2:]))
        elif line.startswith('{"correct"'):
            out.append((notes, json.loads(line)))
            notes = []
    return out


lines = []
for name in sorted(os.listdir(OUT)):
    if not name.endswith(".out"):
        continue
    label = name[:-4]
    # H_hlo_* are the StableHLO hashes; H_set* and H_traced are call H
    call = CALLS.get("Hruns" if label.startswith(("H_set", "H_traced"))
                     else label.split("_")[0], "")
    path = os.path.join(OUT, name)
    for notes, result in runs_of(path):
        head = notes[0] if notes else {}
        merged = {}
        for n in notes[1:]:
            merged.update(n)
        lines.append({"pr": 54, "call": call, "label": label,
                      "tree": "parent" if "parent" in label else "change",
                      "workload": head.get("workload"),
                      "seed": head.get("seed"), "trace": head.get("trace"),
                      "notes": merged, "result": result})
    for line in open(path, errors="replace"):
        line = line.strip()
        if line.startswith(('{"rate_rps"', '{"sabotage"', '{"probe"',
                            '{"cell"')):
            lines.append({"pr": 54, "call": call, "label": label,
                          **json.loads(line)})
with open(os.path.join(HERE, "..", "..", "benchmarks", "records",
                       "pr54.jsonl"), "w") as f:
    for line in lines:
        f.write(json.dumps(line) + "\n")
print(len(lines), "lines")
