# PR 64, call B: `command-a-plus-d4.serve.mixedlen`, the parent (0f6573c
# under .scratch/parent) against the tree from `git archive $(git
# write-tree)` under .scratch/final (the program as committed): six pairs
# with the profiler off, each pair its own seed, the order alternating.
CHANGE=$PWD/.scratch/final TAG=B SEEDS="2164200137 1164300151 3064400163 864500167 1264600179 764700191" sh bench_artifacts/pr64/call_pairs.sh
