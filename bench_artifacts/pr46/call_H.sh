#!/bin/bash
# Call H (PR 46): is the ~100 ms host stall a full garbage collection?
# `chat` on the change, the heap frozen as the worker starts (in the
# probe, not in the program) and not, in turn.
set -x
OUT=$PWD/chiprun_out/pr46; mkdir -p $OUT
for SEED in 2190000201 2190000202 2190000203; do
  python3 bench_artifacts/pr46/chat_probe.py --seed $SEED --freeze 1 >> $OUT/H_chat_freeze.out 2>> $OUT/H.err
  python3 bench_artifacts/pr46/chat_probe.py --seed $SEED --freeze 0 >> $OUT/H_chat_freeze.out 2>> $OUT/H.err
done
grep '^{' $OUT/H_chat_freeze.out
