#!/bin/bash
# Call C (PR 46): the final tree from `git archive $(git write-tree)`
# (unpacked under .scratch/final): six runs of the new cell, each with a
# seed of its own, one traced run, the controls with the final limits.
set -x
OUT=$PWD/chiprun_out/pr46; mkdir -p $OUT
CELL=granite-4.0-h-micro.serve.chatrate
cd .scratch/final
for SEED in 2190000161 2190000162 2190000163 2190000164 2190000165 2190000166; do
  python3 benchmarks/run.py --workload $CELL --seed $SEED --seconds 50 --trace 0 >> $OUT/C_six.out 2>> $OUT/C_six.err; echo "seed $SEED rc=$?"
done
grep -v "^#" $OUT/C_six.out
python3 benchmarks/run.py --workload $CELL --seed 2190000167 --seconds 50 --trace 1 > $OUT/C_traced.out 2> $OUT/C_traced.err; echo "traced rc=$?"
tail -1 $OUT/C_traced.out | cut -c1-5000
python3 bench_artifacts/pr46/sabotage.py --seconds 25 > $OUT/C_sabotage.out 2> $OUT/C_sabotage.err; echo "sabotage rc=$?"
cat $OUT/C_sabotage.out | cut -c1-700
