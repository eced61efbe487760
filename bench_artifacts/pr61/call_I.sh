#!/bin/bash
# PR 61, call I (after the review): the sweep between the two rates of
# call F that stand on either side of sweep.py's rule (1.4 passed, 1.8
# did not), on the tree as it stands
CELL=nemotron-3-nano-30b-a3b-e16.serve.reasoning
OUT=chiprun_out/pr61; mkdir -p $OUT
timeout 1200 python3 benchmarks/sweep.py --workload $CELL --rates ${RATES:-1.5,1.6,1.7} --seconds 50 > $OUT/I_sweep.out 2> $OUT/I_sweep.err; echo "rc=$?"
cat $OUT/I_sweep.out | cut -c1-1300; tail -3 $OUT/I_sweep.err
