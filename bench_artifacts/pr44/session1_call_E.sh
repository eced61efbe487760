set -x
mkdir -p chiprun_out
ROOT=$PWD; A=$ROOT/.scratch/archive; P=$ROOT/.scratch/parent
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/E.err | tee -a $ROOT/chiprun_out/E.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/E.out | cut -c1-${6:-600})
}
W=command-a-plus-d4.serve.mixedlen
run $A archive $W 2146999871 0
run $A archive $W 2145111113 1 4000
i=0
for s in 2144000101 2143000207 2142000311 2141000417; do
  i=$((i+1))
  if [ $((i % 2)) = 1 ]; then run $P parent gpt2-xl.serve.chat $s 0; run $A archive gpt2-xl.serve.chat $s 0; else run $A archive gpt2-xl.serve.chat $s 0; run $P parent gpt2-xl.serve.chat $s 0; fi
done
tail -c 500 chiprun_out/E.err
