#!/bin/bash
# call I (after the review): two sets of six of the new cell from the FINAL
# tree (`git archive $(git write-tree)` under .scratch/pr57_final2) in a
# copy whose BENCHMARK.json ALONE differs: the cell listed under
# serve_itl_p95_ms and under the per-layer metrics that move it (as PR 54
# did), every run with a seed of its own; a traced run of the same copy;
# then chip_smoke.py from the final tree
mkdir -p chiprun_out/pr57
HERE=$(pwd)
CELL=qwen3-next-80b-a3b-d12.serve.longchat
cd .scratch/pr57_final2
: > "$HERE/chiprun_out/pr57/I_itl.out"
for seed in 2157400013 1157400029 3157400041 2157400057 957400063 2157400079 2157500017 1257500023 3057500039 2157500051 857500067 2157500083; do
  python3 benchmarks/run.py --workload $CELL --seed $seed --seconds 50 --trace 0 2> "$HERE/chiprun_out/pr57/I_itl_$seed.err" | sed "s|^|itl $CELL seed=$seed |" >> "$HERE/chiprun_out/pr57/I_itl.out"
  tail -n 1 "$HERE/chiprun_out/pr57/I_itl.out" | cut -c1-420
done
python3 benchmarks/run.py --workload $CELL --seed 2157600011 --seconds 50 --trace 1 > "$HERE/chiprun_out/pr57/I_traced.out" 2> "$HERE/chiprun_out/pr57/I_traced.err"
echo "traced rc=$?"; tail -c 5000 "$HERE/chiprun_out/pr57/I_traced.out"
python3 chip_smoke.py > "$HERE/chiprun_out/pr57/I_chip_smoke.out" 2> "$HERE/chiprun_out/pr57/I_chip_smoke.err"
echo "chip_smoke rc=$?"; tail -c 6000 "$HERE/chiprun_out/pr57/I_chip_smoke.out"
