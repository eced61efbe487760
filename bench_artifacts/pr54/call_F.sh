#!/bin/bash
# PR 54 call F: after the selection bias's scale went from 0.1 to 0.01 and
# the cell left serve_itl_p95_ms out: the final tree from `git archive
# $(git write-tree)`: two sets of six runs of the new cell, each run a seed
# of its own, one traced run, and the check against every broken mechanism.
set -x
mkdir -p chiprun_out/pr54
cd .scratch/final
n=0
for seed in 2254700003 2254700019 2254700033 2254700051 2254700067 2254700081 \
            2254800007 2254800023 2254800041 2254800059 2254800071 2254800089; do
  n=$((n+1)); set=$([ $n -le 6 ] && echo 1 || echo 2)
  python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed $seed --seconds 50 --trace 0 \
      > ../../chiprun_out/pr54/F_set${set}_$seed.out 2> ../../chiprun_out/pr54/F_set${set}_$seed.err
  echo "rc=$?"; tail -1 ../../chiprun_out/pr54/F_set${set}_$seed.out | cut -c1-600
  grep '^# {"requests"' ../../chiprun_out/pr54/F_set${set}_$seed.out | cut -c1-700
done
python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed 2254900013 --seconds 50 --trace 1 \
    > ../../chiprun_out/pr54/F_traced.out 2> ../../chiprun_out/pr54/F_traced.err
echo "rc=$?"; tail -1 ../../chiprun_out/pr54/F_traced.out
python3 bench_artifacts/pr54/sabotage.py --seconds 20 --seed 2254900029 \
    > ../../chiprun_out/pr54/F_sabotage.out 2> ../../chiprun_out/pr54/F_sabotage.err
grep sabotage ../../chiprun_out/pr54/F_sabotage.out | cut -c1-640
