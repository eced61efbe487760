#!/bin/bash
# PR 61, call M (after the review; calls K and L were never given a
# machine, this is both in the order of what they are worth):
# (1) qwen3-next-80b-a3b-d12.serve.longchat, the cell of the five unrun
#     ones most at risk, parent (.scratch/parent) against the final tree,
#     one pair sharing its seed (call_D.sh; files D_*);
# (2) the cell with a window of 100 s at its own rate: a traced run and
#     six runs, each its own seed (files K_*);
# (3) two sets of six at the cell's rate and window (files L_*).
# (2) and (3) run from .scratch/final_k: the final tree with ONE edit for
# these readings alone - the cell appended to serve_tokens_per_s's list
# in BENCHMARK.json, so that the result line carries the completed
# tokens/s the cell does not report.
bash bench_artifacts/pr61/call_D.sh 0 qwen3-next-80b-a3b-d12.serve.longchat:2161700311
TREE=.scratch/final_k bash bench_artifacts/pr61/call_J.sh K 100 1:2161800331 0:1161800347 0:961800359 0:3061800367 0:761800373 0:1261800389 0:2161800397
TREE=.scratch/final_k bash bench_artifacts/pr61/call_J.sh L 50 0:1161900401 0:2161900409 0:861900419 0:3061900421 0:2161900431 0:1261900433 0:2162000439 0:1162000443 0:962000449 0:2162000457 0:3162000461 0:762000463
