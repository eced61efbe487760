# PR 62, call B: `command-a-plus-d4.serve.mixedlen`, the parent (a7eb133
# under .scratch/parent) against the tree from `git archive $(git
# write-tree)` under .scratch/final (the program as committed): six pairs
# with the profiler off, each pair its own seed, the order alternating.
CHANGE=$PWD/.scratch/final TAG=B SEEDS="2162200131 1162300149 3062400157 862500163 1262600173 762700181" sh bench_artifacts/pr62/call_pairs.sh
