# PR 60, call B, parent (d1ce01c under .scratch/pr60_parent, this PR's
# BENCHMARK.json and benchmarks/ laid over it) against the tree from
# `git archive $(git write-tree)` under .scratch/pr60_final: five more
# pairs of `glm-5.2-d5.serve.longctx` (ten with call A's), the parent
# first in the first; the cell COLD once on each side, each run with an
# empty compile cache of its own; one cell the change does not touch,
# `gpt2-xl.serve.chat`, traced on the parent under this PR's benchmark
# files; and `chip_smoke.py` from the final tree.
set -x
ROOT=$PWD; OUT=$ROOT/chiprun_out; mkdir -p $OUT
CHANGE=$ROOT/.scratch/pr60_final TAG=B FIRST=parent SEEDS="2160600181 2160700199 2160800213 2160900227 2161000241" sh bench_artifacts/pr60/call_pairs.sh
i=0
for side in parent final; do
  i=$((i+1)); s=$((2161100300 + i)); name=parent; [ $side = final ] && name=change
  (cd .scratch/pr60_$side && JAX_COMPILATION_CACHE_DIR=$ROOT/.scratch/cold_$i python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed $s --seconds 50 --trace 0 2>> $OUT/pr60_Bcold.err | tee -a $OUT/pr60_Bcold.full | grep "^{" | sed "s|^|$name glm-5.2-d5.serve.longctx seed=$s trace=0 |" | tee -a $OUT/pr60_Bcold.out | cut -c1-700)
  grep "^# {" $OUT/pr60_Bcold.full | tail -n 1 | sed "s|^|$name glm-5.2-d5.serve.longctx seed=$s trace=0 |" >> $OUT/pr60_Bcold.check
done
CELL=gpt2-xl.serve.chat TAG=Bchat TRACE_SEED=2161200311 CHANGE=$ROOT/.scratch/pr60_final sh bench_artifacts/pr60/call_pairs.sh
(cd .scratch/pr60_final && python3 chip_smoke.py > $OUT/pr60_B_smoke.out 2>> $OUT/pr60_B.err; echo "smoke rc=$?")
tail -n 3 $OUT/pr60_B_smoke.out | cut -c1-7000
