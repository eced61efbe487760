#!/bin/bash
# PR 61, call A: (a) the PARENT on the new cell (must fail at once), (b)
# the kernels at the cell's shapes against their oracles, (c) the cell once.
CELL=nemotron-3-nano-30b-a3b-e16.serve.reasoning
OUT=chiprun_out/pr61; mkdir -p $OUT
echo "== parent on the new cell"; t0=$(date +%s)
(cd .scratch/parent && timeout 600 python3 benchmarks/run.py --workload $CELL --seed 3061000007 --seconds 50 --trace 0 > ../../$OUT/A_parent.out 2> ../../$OUT/A_parent.err; echo "rc=$? seconds=$(( $(date +%s) - t0 ))" | tee -a ../../$OUT/A_parent.out)
tail -3 $OUT/A_parent.err
echo "== kernels"
timeout 1200 python3 bench_artifacts/pr61/kernels_only.py > $OUT/A_kernels.out 2> $OUT/A_kernels.err; echo "rc=$?"
tail -c 3000 $OUT/A_kernels.out; tail -5 $OUT/A_kernels.err
echo "== the cell once"
timeout 1500 python3 benchmarks/run.py --workload $CELL --seed 3061000013 --seconds 50 --trace 0 > $OUT/A_cell.out 2> $OUT/A_cell.err; echo "rc=$?"
cat $OUT/A_cell.out | tail -c 6000; tail -5 $OUT/A_cell.err
