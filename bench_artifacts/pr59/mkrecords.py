"""Builds benchmarks/records/pr59.jsonl from the `call_<X>.jsonl` files
PR 59's chip calls left here (run_one.py wrote them): one line a run of
benchmarks/run.py — tree, cell, seed, trace, the result line, the notes,
and for a traced run of the change `stage_sums.py`'s join — with what
the call was."""
import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CALLS = {
    "call_A": "call A: the final tree from `git archive $(git write-tree)` "
              "under .scratch/final — one traced run of each serving cell, "
              "the traced parent (11b8422 with this PR's BENCHMARK.json and "
              "benchmarks/ laid over it) in three cells, two pairs with "
              "the profiler off (call_A.sh)",
    "call_B": "call B: the fixture at its final size; four cells traced "
              "again for the registry's kernels by name; pairs with the "
              "profiler off in the seven cells call A did not pair "
              "(call_B.sh; the programs are call A's)",
    "call_C": "call C: the final tree warm, beside the parent, in the "
              "four cells whose run with the profiler off in call B "
              "compiled anew (call_C.sh)",
    "call_D": "call D, after the review: the final tree from `git archive "
              "$(git write-tree)` — one traced run of each serving cell "
              "once more, with the eleventh metric `scope_moved_pct.serve` "
              "and the recorder's events counted (call_D.sh)",
}
lines = []
for path in sorted(glob.glob(os.path.join(HERE, "call_*.jsonl"))):
    label = os.path.basename(path)[:-6]
    for ln in open(path):
        lines.append(dict(json.loads(ln), call=CALLS.get(label, "")))
with open(os.path.join(HERE, "..", "..", "benchmarks", "records",
                       "pr59.jsonl"), "w") as f:
    for line in lines:
        f.write(json.dumps(line) + "\n")
print(len(lines), "lines")
