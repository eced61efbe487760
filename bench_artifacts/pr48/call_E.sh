# PR 48, chip call E: the final tree from `git archive $(git write-tree)`
# (unpacked under .scratch/pr48_final): six runs of the claimed cell,
# each with a seed of its own (the first compiles `decode` anew: another
# checkout path), one traced run, and `chip_smoke.py` (whose kernels
# phase holds the grouped walk to its oracle on the chip at both cells'
# tiles).
set -x
OUT=$PWD/chiprun_out; mkdir -p $OUT
CELL=granite-4.0-h-micro.serve.chatrate
cd .scratch/pr48_final
run() {
  python3 benchmarks/run.py --workload $CELL --seed $1 --seconds 50 --trace $2 2>> $OUT/pr48_E.err | tee -a $OUT/pr48_E.full | grep "^{" | sed "s|^|final $CELL seed=$1 trace=$2 |" | tee -a $OUT/pr48_E_$3.out | cut -c1-${4:-700}
  grep "top1_agreement" $OUT/pr48_E.full | tail -n 1 | sed "s|^|final $CELL seed=$1 trace=$2 |" | tee -a $OUT/pr48_E_$3.check | cut -c1-400
}
for SEED in 4831000157 4832000269 4833000373 4834000481 4835000591 4836000611; do
  run $SEED 0 six
done
run 4837000703 1 traced 9000
python3 chip_smoke.py > $OUT/pr48_E_smoke.out 2>> $OUT/pr48_E.err; echo "smoke rc=$?"
tail -n 3 $OUT/pr48_E_smoke.out | cut -c1-4000
