#!/bin/bash
# call J (after the review, the last): the FINAL tree as git would commit
# it (`git archive $(git write-tree)` under .scratch/pr57_final3), its own
# BENCHMARK.json: the new cell once traced and once not; the parent
# (77a71cc under .scratch/pr57_parent with this PR's final BENCHMARK.json
# and benchmarks/ laid over it, as the driver does) on the new cell - it
# must fail at once - and on one old cell traced
mkdir -p chiprun_out/pr57
HERE=$(pwd)
CELL=qwen3-next-80b-a3b-d12.serve.longchat
cd .scratch/pr57_final3
python3 benchmarks/run.py --workload $CELL --seed 2157700019 --seconds 50 --trace 1 > "$HERE/chiprun_out/pr57/J_traced.out" 2> "$HERE/chiprun_out/pr57/J_traced.err"
echo "traced rc=$?"; tail -n 1 "$HERE/chiprun_out/pr57/J_traced.out" | cut -c1-2500
python3 benchmarks/run.py --workload $CELL --seed 1157700031 --seconds 50 --trace 0 > "$HERE/chiprun_out/pr57/J_run1.out" 2> "$HERE/chiprun_out/pr57/J_run1.err"
echo "plain rc=$?"; tail -n 1 "$HERE/chiprun_out/pr57/J_run1.out" | cut -c1-800
cd "$HERE/.scratch/pr57_parent"
SECONDS=0
python3 benchmarks/run.py --workload $CELL --seed 2157700043 --seconds 50 --trace 0 > "$HERE/chiprun_out/pr57/J_parent.out" 2>&1
echo "parent on the new cell rc=$? after ${SECONDS}s"; tail -n 2 "$HERE/chiprun_out/pr57/J_parent.out" | cut -c1-300
echo "parent on the new cell: exit code after ${SECONDS} s (above)" >> "$HERE/chiprun_out/pr57/J_parent.out"
python3 benchmarks/run.py --workload granite-4.0-h-micro.serve.chatrate --seed 2157700057 --seconds 50 --trace 1 > "$HERE/chiprun_out/pr57/J_parent_old_traced.out" 2> "$HERE/chiprun_out/pr57/J_parent_old_traced.err"
echo "parent old cell traced rc=$?"; tail -n 1 "$HERE/chiprun_out/pr57/J_parent_old_traced.out" | cut -c1-1800
