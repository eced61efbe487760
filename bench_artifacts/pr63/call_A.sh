#!/bin/bash
# PR 63, call A: (a) the PARENT (git archive of f482e20 with this PR's
# BENCHMARK.json and benchmarks/ laid over it) on the new cell: must fail
# at once; (b) the cell once; (c) the sweep that rates it
# (benchmarks/sweep.py, 50 s a rate).
CELL=lfm2-24b-a2b-e8.serve.assist
OUT=chiprun_out/pr63; mkdir -p $OUT
echo "== parent on the new cell"; t0=$(date +%s)
(cd .scratch/parent && timeout 600 python3 benchmarks/run.py --workload $CELL --seed 3063000007 --seconds 50 --trace 0 > ../../$OUT/A_parent.out 2> ../../$OUT/A_parent.err; echo "rc=$? seconds=$(( $(date +%s) - t0 ))" | tee -a ../../$OUT/A_parent.out)
tail -3 $OUT/A_parent.err
echo "== the cell once"
timeout 1200 python3 benchmarks/run.py --workload $CELL --seed 3063000013 --seconds 50 --trace 0 > $OUT/A_cell.out 2> $OUT/A_cell.err; echo "rc=$?"
tail -c 5000 $OUT/A_cell.out; tail -5 $OUT/A_cell.err
echo "== sweep"
timeout 2000 python3 benchmarks/sweep.py --workload $CELL --rates ${RATES:-5,7,9,11,13} --seconds 50 > $OUT/A_sweep.out 2> $OUT/A_sweep.err; echo "rc=$?"
cat $OUT/A_sweep.out | cut -c1-1300; tail -3 $OUT/A_sweep.err
