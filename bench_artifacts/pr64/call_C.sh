# PR 64, call C: one pair, profiler off, in a cell of each other
# configuration that runs code this PR changed — the parent against the
# final tree (.scratch/final), those most at risk first: `_prefill_kernel`
# serves the full layers' chunks of longchat (3 walks) and reasoning (6);
# chatrate and assist ask the registry's rule for a chunk and keep the
# gather (heads of 64) — then chip_smoke.py whole from the final tree.
i=0
for cell in qwen3-next-80b-a3b-d12.serve.longchat nemotron-3-nano-30b-a3b-e16.serve.reasoning granite-4.0-h-micro.serve.chatrate lfm2-24b-a2b-e8.serve.assist; do
  i=$((i+1))
  first=change; [ $((i % 2)) = 0 ] && first=parent
  CHANGE=$PWD/.scratch/final TAG=C CELL=$cell FIRST=$first SEEDS="21648${i}0197" sh bench_artifacts/pr64/call_pairs.sh
done
(cd .scratch/final && python3 chip_smoke.py 2>$OLDPWD/chiprun_out/pr64_C_smoke.err | tee $OLDPWD/chiprun_out/pr64_C_smoke.out | tail -n 2 | cut -c1-2500)
