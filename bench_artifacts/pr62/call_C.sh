# PR 62, call C: one pair, profiler off, in a cell of each other
# configuration whose decode step runs `_walk_kernel` — the parent against
# the final tree (.scratch/final), the cells most at risk first: grouped
# rows (chatrate, longchat, reasoning), latent rows (chatgen), EVA's two
# runs (longdoc), paged rows (chat) — then chip_smoke.py's kernels phase
# from the final tree.
i=0
for cell in granite-4.0-h-micro.serve.chatrate qwen3-next-80b-a3b-d12.serve.longchat nemotron-3-nano-30b-a3b-e16.serve.reasoning deepseek-v2-lite-d9.serve.chatgen evabyte-d16.serve.longdoc gpt2-xl.serve.chat; do
  i=$((i+1))
  first=change; [ $((i % 2)) = 0 ] && first=parent
  CHANGE=$PWD/.scratch/final TAG=C CELL=$cell FIRST=$first SEEDS="21628${i}0193" sh bench_artifacts/pr62/call_pairs.sh
done
(cd .scratch/final && python3 bench_artifacts/pr62/kernels_only.py 2>/dev/null | tail -n 3 | cut -c1-3000)
