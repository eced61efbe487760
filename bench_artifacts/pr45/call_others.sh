# PR 45: the cells with no routed FFN, one pair each (their programs lower
# to the parent's StableHLO: bench_artifacts/pr45/stablehlo_sha_*.txt),
# parent against change, a pair shares its seed.
set -x
mkdir -p chiprun_out
ROOT=$PWD; C=$ROOT; P=$ROOT/.scratch/pr45_parent; TAG=${TAG:-others}
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/pr45_$TAG.err | tee -a $ROOT/chiprun_out/pr45_$TAG.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr45_$TAG.out | cut -c1-${6:-700})
}
run $P parent gpt2-xl.serve.chat 4510000741 0; run $C change gpt2-xl.serve.chat 4510000741 0
run $C change evabyte-d16.serve.longdoc 4511000843 0; run $P parent evabyte-d16.serve.longdoc 4511000843 0
run $P parent gpt2-xl.serve.overload 4512000947 0; run $C change gpt2-xl.serve.overload 4512000947 0
tail -c 600 chiprun_out/pr45_$TAG.err
