# PR 59, chip call D — after the review: the final tree from the files
# git would commit (.scratch/final), one traced run of each of the seven
# serving cells once more, so that the records hold the eleventh metric
# (`scope_moved_pct.serve`) beside the ten and `stage_sums.py` counts the
# recorder's events against the runner's ring of 1,048,576; then the
# operator's table over the last run's files.  Lines in
# chiprun_out/pr59/call_D.jsonl.
set -x
T0=$(date +%s)
R="python bench_artifacts/pr59/run_one.py call_D"
seed=2159600617
for cell in gpt2-xl.serve.chat deepseek-v2-lite-d9.serve.chatgen glm-5.2-d5.serve.longctx command-a-plus-d4.serve.mixedlen granite-4.0-h-micro.serve.chatrate qwen3-next-80b-a3b-d12.serve.longchat evabyte-d16.serve.longdoc; do
  $R final $cell $seed 1 | tail -n 1 | cut -c1-1800
  seed=$((seed + 10007))
done
(cd .scratch/final && JAX_PLATFORMS=cpu python tools/trace_report.py .bench_tmp/spans --xplane .bench_tmp/trace/plugins/profile/*/*.xplane.pb | cut -c1-120 | head -n 120)
echo elapsed $(( $(date +%s) - T0 ))
