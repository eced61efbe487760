#!/bin/bash
# PR 63, call B: the sweep between the two rates of call A that stood on
# either side of sweep.py's rule (5: yes, 7: no), then the controls that
# must fail (sabotage.py through the cell's own runner, check and
# limits, 20 s a run)
CELL=lfm2-24b-a2b-e8.serve.assist
OUT=chiprun_out/pr63; mkdir -p $OUT
if [ -n "$RATES" ]; then
timeout 1500 python3 benchmarks/sweep.py --workload $CELL --rates $RATES --seconds 50 > $OUT/B_sweep.out 2> $OUT/B_sweep.err; echo "rc=$?"
cat $OUT/B_sweep.out | cut -c1-1300; tail -3 $OUT/B_sweep.err
fi
timeout 2400 python3 bench_artifacts/pr63/sabotage.py --seconds 20 ${ONLY:+--only $ONLY} > $OUT/B_sabotage.out 2> $OUT/B_sabotage.err; echo "rc=$?"
grep '^{' $OUT/B_sabotage.out | cut -c1-900; tail -3 $OUT/B_sabotage.err
