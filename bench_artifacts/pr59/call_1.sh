# PR 59, chip call 1: the fixture, then a first traced run of each
# serving cell on the working tree; stops at the first failure.
set -x
python benchmarks/tests/data/record_scoped_trace.py 2>&1 | grep -v "^\[20" | tail -n 30
[ -s chiprun_out/trace_scoped/serve_scoped.scopes.json ] || exit 1
seed=2159000111
for cell in gpt2-xl.serve.chat deepseek-v2-lite-d9.serve.chatgen glm-5.2-d5.serve.longctx command-a-plus-d4.serve.mixedlen granite-4.0-h-micro.serve.chatrate qwen3-next-80b-a3b-d12.serve.longchat evabyte-d16.serve.longdoc; do
  python bench_artifacts/pr59/run_one.py call_1 change $cell $seed 1
  tail -n 1 chiprun_out/pr59/call_1.jsonl | grep -q '"stage_sums": {' || { tail -n 40 chiprun_out/pr59/call_1.err; exit 1; }
  seed=$((seed + 10007))
done
