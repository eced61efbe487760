#!/bin/bash
# Call B2 (PR 46): the controls again after the convolution's taps were
# drawn as Mamba-2 draws them (call B: the check read the same with the
# state thrown away as with it kept), with the state and the
# convolution's inputs lost in two ways each.
set -x
OUT=chiprun_out/pr46; mkdir -p $OUT
python3 bench_artifacts/pr46/sabotage.py --seconds 25 > $OUT/B2_sabotage.out 2> $OUT/B2_sabotage.err; echo "sabotage rc=$?"
grep -v "^\[" $OUT/B2_sabotage.err | tail -5
cat $OUT/B2_sabotage.out | cut -c1-900
