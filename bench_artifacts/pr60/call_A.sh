# PR 60, call A: the probe (today's loop and the kernel alone at 4, 8 and
# 16 tiles), then `glm-5.2-d5.serve.longctx`, parent against the tree
# from `git archive $(git write-tree)` under .scratch/pr60_final (the
# program as committed; only records and prose were written after it): a
# traced pair and five pairs with the profiler off.
set -x
python bench_artifacts/pr60/probe.py 2>&1 | tail -n 8 | cut -c1-6000
CHANGE=$PWD/.scratch/pr60_final TAG=A TRACE_SEED=2160000113 SEEDS="2160100127 2160200131 2160300149 2160400157 2160500163" sh bench_artifacts/pr60/call_pairs.sh
