#!/bin/bash
# Call F (PR 46): `gpt2-xl.serve.chat` again, two more pairs (change,
# parent, parent, change), notes kept: call D's one pair read the
# first-token p95 49.6 (parent) against 58.2 (change) on programs that
# lower to the same StableHLO.
set -x
OUT=$PWD/chiprun_out/pr46; mkdir -p $OUT
run() {  # side dir seed
  ( cd $2 && python3 benchmarks/run.py --workload gpt2-xl.serve.chat --seed $3 --seconds 50 --trace 0 2>> $OUT/F_$1.err | sed "s/^/$1 $3 /" >> $OUT/F_chat.out )
}
run change . 2190000181
run parent .scratch/parent 2190000181
run parent .scratch/parent 2190000182
run change . 2190000182
grep -v "# {\"workload" $OUT/F_chat.out | cut -c1-1200
