# PR 45, chip calls A and B: one MoE cell, parent against change — a
# traced pair first, then pairs with the profiler off; a pair shares its
# seed, the order alternates.  The parent is the parent commit with this
# PR's benchmark files laid over it (.scratch/pr45_parent), the change is
# the working tree.  CELL, TAG, TRACE_SEED and SEEDS from the environment.
set -x
mkdir -p chiprun_out
ROOT=$PWD; C=$ROOT; P=$ROOT/.scratch/pr45_parent; TAG=${TAG:-A}
CELL=${CELL:-command-a-plus-d4.serve.mixedlen}
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/pr45_$TAG.err | tee -a $ROOT/chiprun_out/pr45_$TAG.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr45_$TAG.out | cut -c1-${6:-700})
}
run $P parent $CELL ${TRACE_SEED:-4500000119} 1 6000
run $C change $CELL ${TRACE_SEED:-4500000119} 1 6000
i=0
for s in ${SEEDS:-4501000213 4502000329 4503000431 4504000547 4505000653}; do
  i=$((i+1))
  if [ $((i % 2)) = 1 ]; then run $C change $CELL $s 0; run $P parent $CELL $s 0; else run $P parent $CELL $s 0; run $C change $CELL $s 0; fi
done
tail -c 600 chiprun_out/pr45_$TAG.err
