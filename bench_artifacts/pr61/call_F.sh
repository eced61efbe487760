#!/bin/bash
# PR 61, call F: after the front matrices went to the kernels turned (the
# layout copy of call C1's trace gone): the sweep again, then the
# unharmed run and the fp8 control (sabotage.py, 20 s each)
CELL=nemotron-3-nano-30b-a3b-e16.serve.reasoning
OUT=chiprun_out/pr61; mkdir -p $OUT
timeout 1500 python3 benchmarks/sweep.py --workload $CELL --rates ${RATES:-1.4,1.8,2.0,2.2,2.6} --seconds 50 > $OUT/F_sweep.out 2> $OUT/F_sweep.err; echo "rc=$?"
cat $OUT/F_sweep.out | cut -c1-1300; tail -3 $OUT/F_sweep.err
timeout 900 python3 bench_artifacts/pr61/sabotage.py --seconds 20 --only none,k_activations_and_rows_at_fp8_e4m3 > $OUT/F_sabotage.out 2> $OUT/F_sabotage.err; echo "rc=$?"
grep '^{' $OUT/F_sabotage.out | cut -c1-900; tail -3 $OUT/F_sabotage.err
