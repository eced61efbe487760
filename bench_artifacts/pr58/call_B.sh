# PR 58, chip call B: the three other cells whose `prefill` holds the
# routed product — a traced pair each, then pairs with the profiler off;
# parent against the FINAL tree from `git archive $(git write-tree)`
# under .scratch/pr58_final (call_pairs.sh with CHANGE set).
set -x
export CHANGE=$PWD/.scratch/pr58_final TAG=B
CELL=command-a-plus-d4.serve.mixedlen TRACE_SEED=2158100119 SEEDS="2158110227 2158120337" bash bench_artifacts/pr58/call_pairs.sh
CELL=deepseek-v2-lite-d9.serve.chatgen TRACE_SEED=2158200113 SEEDS="2158210229" bash bench_artifacts/pr58/call_pairs.sh
CELL=glm-5.2-d5.serve.longctx TRACE_SEED=2158300131 SEEDS="2158310241" bash bench_artifacts/pr58/call_pairs.sh
