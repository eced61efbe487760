#!/bin/bash
# PR 54 call H (after the review): the final tree from `git archive
# $(git write-tree)` at the rate call G's sweep gave: two sets of six
# runs of the new cell, each run a seed of its own, then one traced run.
# The twelve run in a copy of that tree whose BENCHMARK.json has the cell
# on serve_itl_p95_ms's list and nothing else changed, so that the
# result line prints the gap tail too: the same programs, runner, deal
# and check (run.py only chooses which of the runner's numbers to print).
# The traced run is the tree itself.
set -x
mkdir -p chiprun_out/pr54
cp -r .scratch/final .scratch/final_itl
python3 - <<'PY'
import json
path = ".scratch/final_itl/BENCHMARK.json"
b = json.load(open(path))
m = next(m for m in b["end_to_end"] if m["name"] == "serve_itl_p95_ms")
m["workloads"].append("glm-5.2-d5.serve.longctx")
json.dump(b, open(path, "w"), indent=2)
PY
cd .scratch/final_itl
n=0
for seed in 2255100003 2255100019 2255100033 2255100051 2255100067 2255100081 \
            2255200007 2255200023 2255200041 2255200059 2255200071 2255200089; do
  n=$((n+1)); set=$([ $n -le 6 ] && echo 1 || echo 2)
  python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed $seed --seconds 50 --trace 0 \
      > ../../chiprun_out/pr54/H_set${set}_$seed.out 2> ../../chiprun_out/pr54/H_set${set}_$seed.err
  echo "rc=$? at ${SECONDS}s"; tail -1 ../../chiprun_out/pr54/H_set${set}_$seed.out | cut -c1-420
done
cd ../final
if [ $SECONDS -lt 2000 ]; then
  python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed 2255300013 --seconds 50 --trace 1 \
      > ../../chiprun_out/pr54/H_traced.out 2> ../../chiprun_out/pr54/H_traced.err
  echo "rc=$? at ${SECONDS}s"; tail -1 ../../chiprun_out/pr54/H_traced.out
else
  echo "traced run skipped at ${SECONDS}s: the chip-minutes left do not hold it"
fi
