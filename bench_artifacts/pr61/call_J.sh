#!/bin/bash
# PR 61, calls J and M (after the review): the FINAL tree (git archive
# $(git write-tree) under .scratch/final) at the rate sweep.py's rule
# gives (1.36 = 0.8 x 1.7, call I): a traced run, then sets of six, each
# run its own seed (J); with TREE and a window of 100 s, call M.
# usage: call_J.sh <label> <seconds> <trace>:<seed> [<trace>:<seed> ...]
CELL=nemotron-3-nano-30b-a3b-e16.serve.reasoning
OUT=$PWD/chiprun_out/pr61; mkdir -p $OUT
LABEL=$1; SECONDS_=$2; shift 2
cd ${TREE:-.scratch/final} || exit 9
for spec in "$@"; do
  trace=${spec%%:*}; seed=${spec##*:}
  f=$OUT/${LABEL}_t${trace}_${seed}
  timeout 900 python3 benchmarks/run.py --workload $CELL --seed $seed --seconds $SECONDS_ --trace $trace > $f.out 2> $f.err
  echo "rc=$? trace=$trace seed=$seed $(tail -1 $f.out | cut -c1-1500)"
done
