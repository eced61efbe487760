# PR 56, chip call D, what must stand still: `chatrate`, the one other
# cell whose programs call `grouped_attention` (its chunks are refused
# by head size and lower to the parent's bytes) — a traced pair, so that
# `attn_prefill_rows_walked_per_chunk.serve` is read there too, and a
# pair with the profiler off; parent against the final tree.
set -x
CHANGE=$PWD/.scratch/pr56_final TAG=D CELL=granite-4.0-h-micro.serve.chatrate TRACE_SEED=2156500127 SEEDS="2156600239" bash bench_artifacts/pr56/call_pairs.sh
