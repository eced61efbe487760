# PR 47, chip call A: the claimed cell, parent against change — a traced
# pair first, then pairs with the profiler off; a pair shares its seed,
# the order alternates.  The parent is the parent commit (c638f73; this PR
# edits nothing under benchmarks/) unpacked under .scratch/pr47_parent,
# the change is the working tree.  CELL, TAG, TRACE_SEED and SEEDS from
# the environment.
set -x
mkdir -p chiprun_out
ROOT=$PWD; C=$ROOT; P=$ROOT/.scratch/pr47_parent; TAG=${TAG:-A}
CELL=${CELL:-granite-4.0-h-micro.serve.chatrate}
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/pr47_$TAG.err | tee -a $ROOT/chiprun_out/pr47_$TAG.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr47_$TAG.out | cut -c1-${6:-900})
}
if [ -n "${TRACE_SEED-4700000119}" ]; then
  run $P parent $CELL ${TRACE_SEED:-4700000119} 1 7000
  run $C change $CELL ${TRACE_SEED:-4700000119} 1 7000
fi
i=0
for s in ${SEEDS-4701000213 4702000329 4703000431 4704000547 4705000653}; do
  i=$((i+1))
  if [ $((i % 2)) = 1 ]; then run $C change $CELL $s 0; run $P parent $CELL $s 0; else run $P parent $CELL $s 0; run $C change $CELL $s 0; fi
done
tail -c 600 chiprun_out/pr47_$TAG.err
