#!/bin/bash
# Call G (PR 46): which requests make `chat`'s first-token tail, parent
# and change in turn, three pairs.
set -x
OUT=$PWD/chiprun_out/pr46; mkdir -p $OUT
for SEED in 2190000191 2190000192 2190000193; do
  TREE=.scratch/parent python3 bench_artifacts/pr46/chat_probe.py --seed $SEED >> $OUT/G_chat_tail.out 2>> $OUT/G.err
  python3 bench_artifacts/pr46/chat_probe.py --seed $SEED >> $OUT/G_chat_tail.out 2>> $OUT/G.err
done
grep '^{' $OUT/G_chat_tail.out
