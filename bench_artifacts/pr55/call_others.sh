# PR 55, chip call C: what must stand still — one pair (parent against
# change, a seed of its own, the order alternating from cell to cell) of
# each other serving cell: the four that share `_walk` / `_walk_supports`
# (their `decode` programs compile once more on the change side: the
# kernel's source lines moved) and GLM-5.2's, which imports
# `attend_absorbed` and `latent_project`.
set -x
n=0
for pair in gpt2-xl.serve.chat:2155400519 granite-4.0-h-micro.serve.chatrate:2155500623 command-a-plus-d4.serve.mixedlen:2155600711 evabyte-d16.serve.longdoc:2155700827 glm-5.2-d5.serve.longctx:2155800933; do
  n=$((n+1)); first=change; [ $((n % 2)) = 0 ] && first=parent
  TAG=C CELL=${pair%%:*} TRACE_SEED= SEEDS=${pair##*:} FIRST=$first bash bench_artifacts/pr55/call_pairs.sh
done
