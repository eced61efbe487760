#!/bin/bash
# call A: the parent on the new cell (must fail at once), then one traced
# run of the cell at a guessed 2.0 requests/s
mkdir -p chiprun_out/pr57
CELL=qwen3-next-80b-a3b-d12.serve.longchat
( cd .scratch/pr57_parent && date +%s.%N && timeout 600 python3 benchmarks/run.py --workload $CELL --seed 2157000011 --seconds 50 --trace 0 ; echo "parent rc=$?"; date +%s.%N ) > chiprun_out/pr57/A_parent.out 2>&1
tail -5 chiprun_out/pr57/A_parent.out
python3 benchmarks/run.py --workload $CELL --seed 2157000013 --seconds 50 --trace 1 > chiprun_out/pr57/A_traced.out 2> chiprun_out/pr57/A_traced.err
echo "traced rc=$?"
tail -c 6000 chiprun_out/pr57/A_traced.out
tail -5 chiprun_out/pr57/A_traced.err
