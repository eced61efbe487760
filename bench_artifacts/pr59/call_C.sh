# PR 59, chip call C — the final tree (.scratch/final) once more, warm,
# in the four cells whose only run with the profiler off in call B
# compiled anew (a first run after this PR compiles every program that
# holds a Mosaic kernel once: the registry's scope moves the kernel's
# source locations, as any edit above a call site does), the parent
# beside it; and the fixture's table through the operator's tool.
set -x
R="python bench_artifacts/pr59/run_one.py call_C"
seed=2159500523
for cell in bert-large.train.seq128 gpt2-xl-d24.train.seq1024 evabyte-d16.serve.longdoc glm-5.2-d5.serve.longctx; do
  $R final $cell $seed 0 | tail -n 1 | cut -c1-600
  $R parent $cell $seed 0 | tail -n 1 | cut -c1-600
  seed=$((seed + 10007))
done
