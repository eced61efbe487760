#!/bin/bash
# Call B (PR 46): the prefill chunk chosen on the chip (256 / 512 / 1,024
# at one rate), a finer sweep round the knee, then the controls through
# the cell's own check.  RATE and RATES come from call A's sweep.
set -x
OUT=chiprun_out/pr46; mkdir -p $OUT
RATE=${RATE:-5}; RATES=${RATES:-6,8}
for CHUNK in 256 1024; do
  python3 bench_artifacts/pr46/probe.py --rates $RATE --seconds 50 --seed 2190000151 \
      --serve "{\"prefill_chunk\": $CHUNK}" >> $OUT/B_chunk.out 2>> $OUT/B_chunk.err; echo "chunk $CHUNK rc=$?"
done
cat $OUT/B_chunk.out
python3 bench_artifacts/pr46/probe.py --rates $RATES --seconds 50 --seed 2190000152 > $OUT/B_sweep.out 2> $OUT/B_sweep.err; echo "sweep rc=$?"
cat $OUT/B_sweep.out
python3 bench_artifacts/pr46/sabotage.py --seconds 25 > $OUT/B_sabotage.out 2> $OUT/B_sabotage.err; echo "sabotage rc=$?"
grep -v "^\[" $OUT/B_sabotage.err | tail -5
cat $OUT/B_sabotage.out
