#!/bin/bash
# call B: the probe (each registry op against its oracle, through the
# programs, against the reference), then the sweep on the tree as it stands
mkdir -p chiprun_out/pr57
python3 bench_artifacts/pr57/probe.py --prompt 4096 --steps 64 --slots 4 --seq 6144 > chiprun_out/pr57/B_probe.out 2> chiprun_out/pr57/B_probe.err
echo "probe rc=$?"; grep "^{\|^#" chiprun_out/pr57/B_probe.out | cut -c1-600
python3 benchmarks/sweep.py --workload qwen3-next-80b-a3b-d12.serve.longchat --rates 1.5,2.0,2.5,3.0 --seconds 50 > chiprun_out/pr57/B_sweep.out 2> chiprun_out/pr57/B_sweep.err
echo "sweep rc=$?"; grep "^{" chiprun_out/pr57/B_sweep.out | cut -c1-1200; tail -3 chiprun_out/pr57/B_sweep.err | cut -c1-400
