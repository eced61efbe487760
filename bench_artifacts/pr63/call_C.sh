#!/bin/bash
# PR 63, call C: the cell at its rate from the tree TREE (default: the
# working tree): runs of `seconds`, each its own seed.
# usage: call_C.sh <label> <seconds> <trace>:<seed> [<trace>:<seed> ...]
CELL=lfm2-24b-a2b-e8.serve.assist
OUT=$PWD/chiprun_out/pr63; mkdir -p $OUT
LABEL=$1; SECONDS_=$2; shift 2
cd ${TREE:-.} || exit 9
for spec in "$@"; do
  trace=${spec%%:*}; seed=${spec##*:}
  f=$OUT/${LABEL}_t${trace}_${seed}
  timeout 900 python3 benchmarks/run.py --workload $CELL --seed $seed --seconds $SECONDS_ --trace $trace > $f.out 2> $f.err
  echo "rc=$? trace=$trace seed=$seed $(tail -1 $f.out | cut -c1-1500)"
done
