#!/bin/bash
# PR 61, call C: the FINAL tree (git archive $(git write-tree) under
# .scratch/final): a traced run, then sets of six, each run its own seed.
# usage: call_C.sh <label> <trace 0|1> <seed> [<seed> ...]
CELL=nemotron-3-nano-30b-a3b-e16.serve.reasoning
OUT=$PWD/chiprun_out/pr61; mkdir -p $OUT
LABEL=$1; shift
cd .scratch/final || exit 9
for spec in "$@"; do
  trace=${spec%%:*}; seed=${spec##*:}
  f=$OUT/${LABEL}_t${trace}_${seed}
  timeout 900 python3 benchmarks/run.py --workload $CELL --seed $seed --seconds 50 --trace $trace > $f.out 2> $f.err
  echo "rc=$? trace=$trace seed=$seed $(tail -1 $f.out | cut -c1-1500)"
done
