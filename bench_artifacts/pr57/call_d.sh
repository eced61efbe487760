#!/bin/bash
# call D: the decode walk over two K/V heads after the repair, then a
# traced run of the cell
mkdir -p chiprun_out/pr57
python3 bench_artifacts/pr57/walk_probe.py > chiprun_out/pr57/D_walk.out 2> chiprun_out/pr57/D_walk.err
echo "walk rc=$?"; grep "^{" chiprun_out/pr57/D_walk.out | cut -c1-330
python3 benchmarks/run.py --workload qwen3-next-80b-a3b-d12.serve.longchat --seed 2157000017 --seconds 50 --trace 1 > chiprun_out/pr57/D_traced.out 2> chiprun_out/pr57/D_traced.err
echo "traced rc=$?"; tail -c 5000 chiprun_out/pr57/D_traced.out
