#!/bin/bash
# PR 63, call E: from the tree git would commit (.scratch/final: git
# archive of the write-tree named in final_tree_of_call_E.txt):
# chip_smoke.py, then the new cell once, traced.
OUT=$PWD/chiprun_out/pr63; mkdir -p $OUT
cd .scratch/final || exit 9
timeout 1500 python3 chip_smoke.py > $OUT/E_chip_smoke.out 2> $OUT/E_chip_smoke.err; echo "chip_smoke rc=$?"
tail -c 2500 $OUT/E_chip_smoke.out; tail -3 $OUT/E_chip_smoke.err
timeout 900 python3 benchmarks/run.py --workload lfm2-24b-a2b-e8.serve.assist --seed 2163600613 --seconds 50 --trace 1 > $OUT/E_t1_2163600613.out 2> $OUT/E_t1_2163600613.err; echo "rc=$? $(tail -1 $OUT/E_t1_2163600613.out | cut -c1-600)"
