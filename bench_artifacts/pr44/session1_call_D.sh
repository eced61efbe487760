set -x
mkdir -p chiprun_out
ROOT=$PWD
P=$ROOT/.scratch/parent
NB=$ROOT/.scratch/parent_nb
rm -rf $NB; cp -r $P $NB; cp BENCHMARK.json $NB/; cp -r benchmarks/. $NB/benchmarks/
run() {  # dir tag workload seed trace
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/D.err | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/D.out | cut -c1-${6:-700})
}
# the parent on the new cell: must fail at once
(cd $NB && timeout 300 python3 benchmarks/run.py --workload command-a-plus-d4.serve.mixedlen --seed 2147483111 --seconds 50 --trace 0 > $ROOT/chiprun_out/D_parent_newcell.out 2> $ROOT/chiprun_out/D_parent_newcell.err; echo "parent on new cell: exit=$? after ${SECONDS}s"; tail -3 $ROOT/chiprun_out/D_parent_newcell.err | cut -c1-400)
python3 .scratch/sabotage.py --seconds 25 --only h_product_inputs_rounded_to_fp8_e4m3 2>> chiprun_out/sabD.err | tee chiprun_out/sabD.out | grep -v "^\[20\|^#" | cut -c1-700
i=0
for W in gpt2-xl.serve.chat deepseek-v2-lite-d9.serve.chatgen evabyte-d16.serve.longdoc gpt2-xl.serve.overload; do
  i=$((i+1))
  s1=$((2147480000 + 1013 * i)); s2=$((2140000000 + 7717 * i))
  run $P parent $W $s1 0; run $ROOT change $W $s1 0; run $ROOT change $W $s2 0; run $P parent $W $s2 0
done
for W in gpt2-xl.serve.chat deepseek-v2-lite-d9.serve.chatgen; do
  s=$((2130000000 + ${#W} * 104729))
  run $NB parent_nb $W $s 1 2500; run $ROOT change $W $s 1 2500
done
tail -c 600 chiprun_out/D.err
