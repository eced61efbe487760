# PR 44, second session, chip call B: the committed files are enough (the
# new cell traced, from `git archive $(git write-tree)`), then
# gpt2-xl.serve.chat parent against change, eight more pairs, a pair shares
# its seed, the order alternating.
set -x
mkdir -p chiprun_out
ROOT=$PWD; A=$ROOT/.scratch/archive; P=$ROOT/.scratch/parent
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/s2B.err | tee -a $ROOT/chiprun_out/s2B.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/s2B.out | cut -c1-${6:-600})
}
run $A archive command-a-plus-d4.serve.mixedlen 2133001131 1 4000
i=0
for s in 2139000503 2138000609 2137000711 2136000817 2135000923 2134001029 2132001237 2131001341; do
  i=$((i+1))
  if [ $((i % 2)) = 1 ]; then run $A archive gpt2-xl.serve.chat $s 0; run $P parent gpt2-xl.serve.chat $s 0; else run $P parent gpt2-xl.serve.chat $s 0; run $A archive gpt2-xl.serve.chat $s 0; fi
done
tail -c 500 chiprun_out/s2B.err
