#!/bin/bash
# call H: the two nearest cells once each on parent and change (parent =
# 77a71cc under .scratch/pr57_parent with this PR's BENCHMARK.json and
# benchmarks/ laid over it, as the driver does; change = the final tree
# under .scratch/pr57_final), then a traced run of the new cell from the
# final tree with what PERF.md quotes from its trace
mkdir -p chiprun_out/pr57
HERE=$(pwd)
: > chiprun_out/pr57/H_others.out
run() {  # side dir cell seed trace
  ( cd "$2" && python3 benchmarks/run.py --workload "$3" --seed "$4" --seconds 50 --trace "$5" 2> "$HERE/chiprun_out/pr57/H_$1_$4.err" | sed "s|^|$1 $3 seed=$4 |" ) >> chiprun_out/pr57/H_others.out
  tail -n 1 chiprun_out/pr57/H_others.out | cut -c1-500
}
run parent .scratch/pr57_parent granite-4.0-h-micro.serve.chatrate 2157000211 0
run change .scratch/pr57_final granite-4.0-h-micro.serve.chatrate 2157000211 0
run change .scratch/pr57_final command-a-plus-d4.serve.mixedlen 2157000223 0
run parent .scratch/pr57_parent command-a-plus-d4.serve.mixedlen 2157000223 0
cd .scratch/pr57_final
python3 benchmarks/run.py --workload qwen3-next-80b-a3b-d12.serve.longchat --seed 2157300029 --seconds 50 --trace 1 > "$HERE/chiprun_out/pr57/H_traced.out" 2> "$HERE/chiprun_out/pr57/H_traced.err"
echo "traced rc=$?"; tail -c 3500 "$HERE/chiprun_out/pr57/H_traced.out"
python3 bench_artifacts/pr57/trace_numbers.py > "$HERE/chiprun_out/pr57/H_trace_numbers.out" 2>> "$HERE/chiprun_out/pr57/H_traced.err"
cut -c1-3000 "$HERE/chiprun_out/pr57/H_trace_numbers.out"
