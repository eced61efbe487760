# PR 55, chip call E: the final tree from `git archive $(git write-tree)`
# (unpacked under .scratch/pr55_final): six runs of the claimed cell,
# each with a seed of its own (the first compiles `decode` anew: another
# checkout path), one traced run, and `chip_smoke.py` (whose kernels
# phase holds the latent walk to its oracle on the chip at the cell's
# tile, beside the other walks).
set -x
OUT=$PWD/chiprun_out; mkdir -p $OUT
CELL=deepseek-v2-lite-d9.serve.chatgen
cd .scratch/pr55_final
run() {
  python3 benchmarks/run.py --workload $CELL --seed $1 --seconds 50 --trace $2 2>> $OUT/pr55_E.err | tee -a $OUT/pr55_E.full | grep "^{" | sed "s|^|final $CELL seed=$1 trace=$2 |" | tee -a $OUT/pr55_E_$3.out | cut -c1-${4:-700}
  grep "^# {" $OUT/pr55_E.full | tail -n 1 | sed "s|^|final $CELL seed=$1 trace=$2 |" | tee -a $OUT/pr55_E_$3.check | cut -c1-400
}
for SEED in 2155910157 2155920269 2155930373 2155940481 2155950591 2155960611; do
  run $SEED 0 six
done
run 2155970703 1 traced 12000
python3 chip_smoke.py > $OUT/pr55_E_smoke.out 2>> $OUT/pr55_E.err; echo "smoke rc=$?"
tail -n 3 $OUT/pr55_E_smoke.out | cut -c1-6000
