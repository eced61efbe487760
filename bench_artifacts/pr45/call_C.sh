# PR 45, chip call C: the committed files are enough.  From the final
# tree as `git archive $(git write-tree)` gives it (.scratch/pr45_archive):
# both MoE cells traced, the claimed cell six fresh seeds, chip_smoke.py.
set -x
mkdir -p chiprun_out
ROOT=$PWD; A=$ROOT/.scratch/pr45_archive; TAG=C
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/pr45_$TAG.err | tee -a $ROOT/chiprun_out/pr45_$TAG.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr45_$TAG.out | cut -c1-${6:-700})
}
run $A archive command-a-plus-d4.serve.mixedlen 4530000131 1 3000
run $A archive deepseek-v2-lite-d9.serve.chatgen 4531000237 1 3000
for s in 4532000339 4533000443 4534000559 4535000661 4536000767 4537000871; do
  run $A archive command-a-plus-d4.serve.mixedlen $s 0
done
(cd $A && python3 chip_smoke.py 2>> $ROOT/chiprun_out/pr45_C_smoke.err | tee $ROOT/chiprun_out/pr45_C_smoke.out | cut -c1-1500 | tail -8)
tail -c 600 chiprun_out/pr45_$TAG.err
