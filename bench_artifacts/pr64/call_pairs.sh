# PR 64, a chip call of pairs (PR 62's script): one cell, parent against
# change — a traced pair first (TRACE_SEED, empty: none), then pairs with
# the profiler off (SEEDS); a pair shares its seed, the order alternates.
# The parent is the parent commit (0f6573c) unpacked under .scratch/parent
# (this PR adds no benchmark file to lay over it); the change is the
# working tree (CHANGE: another directory, the final tree's).  CELL, TAG,
# TRACE_SEED and SEEDS from the environment (FIRST=parent: the first pair
# runs the parent first).  `.out`: the result lines; `.check`: each run's
# `# {...}` line of the comparison that decides `correct`.
set -x
mkdir -p chiprun_out
ROOT=$PWD; C=${CHANGE:-$ROOT}; P=$ROOT/.scratch/parent; TAG=${TAG:-A}
CELL=${CELL:-command-a-plus-d4.serve.mixedlen}
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/pr64_$TAG.err | tee -a $ROOT/chiprun_out/pr64_$TAG.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr64_$TAG.out | cut -c1-${6:-900})
  grep "^# {" $ROOT/chiprun_out/pr64_$TAG.full | tail -n 1 | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr64_$TAG.check | cut -c1-600
}
if [ -n "${TRACE_SEED-}" ]; then
  run $P parent $CELL $TRACE_SEED 1 9000
  run $C change $CELL $TRACE_SEED 1 9000
fi
i=0; [ "${FIRST:-change}" = parent ] && i=1
for s in ${SEEDS-}; do
  i=$((i+1))
  if [ $((i % 2)) = 1 ]; then run $C change $CELL $s 0; run $P parent $CELL $s 0; else run $P parent $CELL $s 0; run $C change $CELL $s 0; fi
done
tail -c 600 chiprun_out/pr64_$TAG.err
