#!/bin/bash
# PR 61, call B: the sweep that rates the cell (benchmarks/sweep.py, 50 s
# a rate), then the controls that must fail (sabotage.py, 20 s each)
CELL=nemotron-3-nano-30b-a3b-e16.serve.reasoning
OUT=chiprun_out/pr61; mkdir -p $OUT
timeout 1500 python3 benchmarks/sweep.py --workload $CELL --rates ${RATES:-1.0,1.2,1.4,1.6,2.0} --seconds 50 > $OUT/B_sweep.out 2> $OUT/B_sweep.err; echo "rc=$?"
cat $OUT/B_sweep.out | cut -c1-1300; tail -3 $OUT/B_sweep.err
timeout 1900 python3 bench_artifacts/pr61/sabotage.py --seconds 20 --only ${ONLY:-none,k_activations_and_rows_at_fp8_e4m3,a_state_thrown_away_between_scan_chunks,c_group_zero_b_and_c_for_every_head,d_silu_for_relu_squared,b_seated_slot_keeps_its_last_tenants_state} > $OUT/B_sabotage.out 2> $OUT/B_sabotage.err; echo "rc=$?"
grep '^{' $OUT/B_sabotage.out | cut -c1-900; tail -3 $OUT/B_sabotage.err
