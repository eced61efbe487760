#!/bin/bash
# Call D (PR 46): one cell of each other served configuration on the
# parent and on the change, one pair a cell (parent, change), each pair
# with a seed of its own; the programs lower to the parent's StableHLO
# (stablehlo_sha_*.txt), so a pair is a check, not a measurement of a
# difference.
set -x
OUT=$PWD/chiprun_out/pr46; mkdir -p $OUT
SEED=2190000170
run() {  # side dir cell seed
  ( cd $2 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace 0 2>> $OUT/D_$1.err | tail -1 | sed "s/^/$1 $3 $4 /" >> $OUT/D_others.out )
}
for CELL in command-a-plus-d4.serve.mixedlen deepseek-v2-lite-d9.serve.chatgen gpt2-xl.serve.chat evabyte-d16.serve.longdoc; do
  SEED=$((SEED + 1))
  run parent .scratch/parent $CELL $SEED
  run change . $CELL $SEED
done
cat $OUT/D_others.out | cut -c1-900
