# PR 64, call D: the claimed cell again — a second traced pair, the
# change first this time, then runs of the final tree alone, each a
# fresh seed (the check's readings and the share over a dozen runs).
set -x
mkdir -p chiprun_out
ROOT=$PWD; C=$ROOT/.scratch/final; P=$ROOT/.scratch/parent; TAG=D
CELL=command-a-plus-d4.serve.mixedlen
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace $5 2>> $ROOT/chiprun_out/pr64_$TAG.err | tee -a $ROOT/chiprun_out/pr64_$TAG.full | grep "^{" | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr64_$TAG.out | cut -c1-${6:-900})
  grep "^# {" $ROOT/chiprun_out/pr64_$TAG.full | tail -n 1 | sed "s|^|$2 $3 seed=$4 trace=$5 |" | tee -a $ROOT/chiprun_out/pr64_$TAG.check | cut -c1-600
}
run $C change $CELL 2164900199 1 6000
run $P parent $CELL 2164900199 1 6000
for s in ${SEEDS-1165000211 3065100227 865200229 1265300233}; do run $C change $CELL $s 0; done
tail -c 400 chiprun_out/pr64_$TAG.err
