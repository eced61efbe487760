#!/bin/bash
# PR 54 call D: the final tree from `git archive $(git write-tree)`:
# two sets of six runs of the new cell, each run a seed of its own, and
# one traced run.
set -x
mkdir -p chiprun_out/pr54
cd .scratch/final
n=0
for seed in 2254100003 2254100019 2254100033 2254100051 2254100067 2254100081 \
            2254200007 2254200023 2254200041 2254200059 2254200071 2254200089; do
  n=$((n+1)); set=$([ $n -le 6 ] && echo 1 || echo 2)
  python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed $seed --seconds 50 --trace 0 \
      > ../../chiprun_out/pr54/D_set${set}_$seed.out 2> ../../chiprun_out/pr54/D_set${set}_$seed.err
  echo "rc=$?"; tail -1 ../../chiprun_out/pr54/D_set${set}_$seed.out | cut -c1-700
done
python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed 2254300013 --seconds 50 --trace 1 \
    > ../../chiprun_out/pr54/D_traced.out 2> ../../chiprun_out/pr54/D_traced.err
echo "rc=$?"; tail -1 ../../chiprun_out/pr54/D_traced.out
