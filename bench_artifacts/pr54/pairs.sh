#!/bin/bash
# pairs.sh <label> <cell> <seed>...: parent against change on one accepted
# cell, alternating (parent, change, change, parent, ...), each pair a seed
# of its own; parent = .scratch/parent (the parent commit with this PR's
# benchmark files laid over it), change = .scratch/final (git archive of
# the final tree).
label=$1; cell=$2; shift 2
mkdir -p chiprun_out/pr54
i=0
for seed in "$@"; do
  i=$((i+1))
  if [ $((i % 2)) -eq 1 ]; then order="parent final"; else order="final parent"; fi
  for tree in $order; do
    ( cd .scratch/$tree && python3 benchmarks/run.py --workload $cell --seed $seed --seconds 50 --trace 0 \
        > ../../chiprun_out/pr54/${label}_${tree}_$seed.out 2> ../../chiprun_out/pr54/${label}_${tree}_$seed.err )
    echo "$tree $seed $(tail -1 chiprun_out/pr54/${label}_${tree}_$seed.out | cut -c1-420)"
    grep '^# {"requests"' chiprun_out/pr54/${label}_${tree}_$seed.out | cut -c1-200
  done
done
