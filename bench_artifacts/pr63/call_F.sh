#!/bin/bash
# PR 63, call F: the cell on serve_tokens_per_s's list too (and on the
# two per-layer metrics that move it), logit_margin 2.0: a traced run and
# two sets of six, every run a seed of its own - the sets the cell's
# end-to-end metrics are admitted by; then chip_smoke.py from the tree
# git would commit (.scratch/final: git archive of the write-tree named
# in final_tree_of_call_F.txt).
bash bench_artifacts/pr63/call_C.sh F1 50 1:2163400413 0:1163400427 0:2163400439 0:863400451 0:3063400463 0:2163400477 0:1263400489
bash bench_artifacts/pr63/call_C.sh F2 50 0:2163500511 0:1163500523 0:963500539 0:2163500551 0:3163500563 0:763500577
OUT=$PWD/chiprun_out/pr63
(cd .scratch/final && timeout 1200 python3 chip_smoke.py > $OUT/F_chip_smoke.out 2> $OUT/F_chip_smoke.err; echo "chip_smoke rc=$?")
tail -c 1500 $OUT/F_chip_smoke.out; tail -5 $OUT/F_chip_smoke.err
