#!/bin/bash
# PR 54 call A: (a) the parent commit on the new cell (must fail at once),
# then the change on the new cell, traced, at a first guess of the rate.
set -x
mkdir -p chiprun_out/pr54
( cd .scratch/parent && timeout 300 python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed 2254000011 --seconds 50 --trace 0 \
    > ../../chiprun_out/pr54/A_parent_newcell.out 2> ../../chiprun_out/pr54/A_parent_newcell.err; echo "parent rc=$?" | tee -a ../../chiprun_out/pr54/A_parent_newcell.out )
tail -3 chiprun_out/pr54/A_parent_newcell.err
python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed 2254000013 --seconds 50 --trace 1 \
    > chiprun_out/pr54/A_traced.out 2> chiprun_out/pr54/A_traced.err; echo "change rc=$?"
tail -c 6000 chiprun_out/pr54/A_traced.out
tail -c 3000 chiprun_out/pr54/A_traced.err
python3 bench_artifacts/pr54/select_probe.py > chiprun_out/pr54/A_select_probe.out 2>&1; cat chiprun_out/pr54/A_select_probe.out | grep probe
