# PR 59, chip call B — from the files git would commit (.scratch/final):
# the fixture again at a size whose gaps between operations are under
# 2 % of a run; four cells traced once more for the registry's kernels
# by name (stage_sums.py's `registry_ms`); then pairs with the profiler
# off in the cells call A did not pair.  Lines in
# chiprun_out/pr59/call_B.jsonl.
set -x
T0=$(date +%s)
left() { [ $(( $(date +%s) - T0 )) -lt ${1} ]; }
R="python bench_artifacts/pr59/run_one.py call_B"
(cd .scratch/final && python benchmarks/tests/data/record_scoped_trace.py 2>&1 | grep -v "^\[20" | tail -n 60 | cut -c1-400)
mkdir -p chiprun_out/trace_scoped
cp .scratch/final/chiprun_out/trace_scoped/serve_scoped.* chiprun_out/trace_scoped/
seed=2159300311
for cell in deepseek-v2-lite-d9.serve.chatgen qwen3-next-80b-a3b-d12.serve.longchat granite-4.0-h-micro.serve.chatrate command-a-plus-d4.serve.mixedlen; do
  $R final $cell $seed 1 | tail -n 2 | cut -c1-1500
  seed=$((seed + 10007))
done
seed=2159400419
for cell in qwen3-next-80b-a3b-d12.serve.longchat command-a-plus-d4.serve.mixedlen granite-4.0-h-micro.serve.chatrate glm-5.2-d5.serve.longctx evabyte-d16.serve.longdoc gpt2-xl-d24.train.seq1024 bert-large.train.seq128; do
  left 2900 && { $R final $cell $seed 0 | tail -n 1 | cut -c1-600; $R parent $cell $seed 0 | tail -n 1 | cut -c1-600; }
  seed=$((seed + 10007))
done
echo elapsed $(( $(date +%s) - T0 ))
