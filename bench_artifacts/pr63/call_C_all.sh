#!/bin/bash
# PR 63, call C: after W_q and W_k were re-drawn (qk_scale 4) and the
# cell put at 4.0 requests/s (0.8 x 5.0, sweep.py's rule): the unharmed
# run and sabotage (iv) again, then a traced run and two sets of six,
# every run a seed of its own.
OUT=chiprun_out/pr63; mkdir -p $OUT
timeout 900 python3 bench_artifacts/pr63/sabotage.py --seconds 20 --only none,iv_q_and_k_not_normed > $OUT/C_sabotage.out 2> $OUT/C_sabotage.err; echo "rc=$?"
grep '^{' $OUT/C_sabotage.out | cut -c1-900; tail -3 $OUT/C_sabotage.err
bash bench_artifacts/pr63/call_C.sh C1 50 1:2163100113 0:1163100127 0:2163100139 0:863100151 0:3063100163 0:2163100177 0:1263100189
bash bench_artifacts/pr63/call_C.sh C2 50 0:2163200211 0:1163200223 0:963200239 0:2163200251 0:3163200263 0:763200277
