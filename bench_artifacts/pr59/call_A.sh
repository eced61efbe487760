# PR 59, chip call A — everything from the files git would commit
# (.scratch/final = `git archive $(git write-tree)`; mkfinal.sh): the
# fixture; one traced run of each serving cell; the traced parent in four
# cells (what a scope costs at run time: nothing); then pairs with the
# profiler off while the call's hour lasts.  Lines in
# chiprun_out/pr59/call_A.jsonl.
set -x
T0=$(date +%s)
left() { [ $(( $(date +%s) - T0 )) -lt ${1} ]; }
R="python bench_artifacts/pr59/run_one.py call_A"
(cd .scratch/final && python benchmarks/tests/data/record_scoped_trace.py 2>&1 | grep -v "^\[20" | tail -n 30)
mkdir -p chiprun_out/trace_scoped
cp .scratch/final/chiprun_out/trace_scoped/serve_scoped.* chiprun_out/trace_scoped/
[ -s chiprun_out/trace_scoped/serve_scoped.scopes.json ] || exit 1
seed=2159100113
for cell in gpt2-xl.serve.chat deepseek-v2-lite-d9.serve.chatgen glm-5.2-d5.serve.longctx command-a-plus-d4.serve.mixedlen granite-4.0-h-micro.serve.chatrate qwen3-next-80b-a3b-d12.serve.longchat evabyte-d16.serve.longdoc; do
  $R final $cell $seed 1
  tail -n 1 chiprun_out/pr59/call_A.jsonl | grep -q '"stage_sums": {' || { tail -n 40 chiprun_out/pr59/call_A.err; [ $cell = gpt2-xl.serve.chat ] && exit 1; }
  seed=$((seed + 10007))
done
seed=2159100113
for cell in gpt2-xl.serve.chat deepseek-v2-lite-d9.serve.chatgen; do
  left 2500 && $R parent $cell $seed 1
  seed=$((seed + 10007))
done
left 2700 && $R parent granite-4.0-h-micro.serve.chatrate 2159140141 1
left 2800 && $R parent gpt2-xl.serve.chat 2159200219 0
left 2900 && $R final gpt2-xl.serve.chat 2159200219 0
left 3000 && $R final deepseek-v2-lite-d9.serve.chatgen 2159210227 0
left 3100 && $R parent deepseek-v2-lite-d9.serve.chatgen 2159210227 0
echo elapsed $(( $(date +%s) - T0 ))
