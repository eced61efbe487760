# PR 47, chip call C: the final tree from `git archive $(git write-tree)`
# (unpacked under .scratch/pr47_final): six runs of the claimed cell,
# each with a seed of its own, one traced run, and `chip_smoke.py` (whose
# kernels phase holds the `ssm_step` kernel to its oracle on the chip).
set -x
OUT=$PWD/chiprun_out; mkdir -p $OUT
CELL=granite-4.0-h-micro.serve.chatrate
cd .scratch/pr47_final
for SEED in 4711000159 4712000263 4713000371 4714000487 4715000593 4716000607; do
  python3 benchmarks/run.py --workload $CELL --seed $SEED --seconds 50 --trace 0 2>> $OUT/pr47_C.err | grep "^{" | tee -a $OUT/pr47_C_six.out | cut -c1-700; echo "seed $SEED rc=$?"
done
python3 benchmarks/run.py --workload $CELL --seed 4717000709 --seconds 50 --trace 1 2>> $OUT/pr47_C.err | grep "^{" | tee -a $OUT/pr47_C_traced.out | cut -c1-7000
python3 chip_smoke.py > $OUT/pr47_C_smoke.out 2>> $OUT/pr47_C.err; echo "smoke rc=$?"
tail -n 3 $OUT/pr47_C_smoke.out | cut -c1-3000
