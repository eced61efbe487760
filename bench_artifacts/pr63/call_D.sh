#!/bin/bash
# PR 63, call D: other serving cells, parent (.scratch/parent, this PR's
# benchmark files laid over it) against the final tree (.scratch/final),
# a pair a cell sharing its seed, the order alternating.
# usage: call_D.sh <trace> <cell>:<seed> ...
OUT=$PWD/chiprun_out/pr63; mkdir -p $OUT
TRACE=$1; shift
n=0
for spec in "$@"; do
  cell=${spec%%:*}; seed=${spec##*:}
  if [ $((n % 2)) -eq 0 ]; then order="parent final"; else order="final parent"; fi
  n=$((n + 1))
  for side in $order; do
    f=$OUT/D_${side}_t${TRACE}_${cell}
    (cd .scratch/$side && timeout 900 python3 benchmarks/run.py --workload $cell --seed $seed --seconds 50 --trace $TRACE > $f.out 2> $f.err)
    echo "rc=$? $side $cell seed=$seed $(tail -1 $f.out | cut -c1-900)"
  done
done
