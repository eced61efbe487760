# PR 56, chip call C: the final tree from `git archive $(git write-tree)`
# (unpacked under .scratch/pr56_final; its kernel loops over the K/V
# heads where calls A and B's unrolled them) against the parent — a
# traced pair and five pairs of the claimed cell (call_pairs.sh with
# CHANGE set), then `chip_smoke.py` from the final tree, whose kernels
# phase holds the prefill walk to its oracle at the cell's shape.
set -x
CHANGE=$PWD/.scratch/pr56_final TAG=C TRACE_SEED=2156800121 SEEDS="2156810233 2156820347 2156830457 2156840569 2156850673" bash bench_artifacts/pr56/call_pairs.sh
OUT=$PWD/chiprun_out
(cd .scratch/pr56_final && python3 chip_smoke.py > $OUT/pr56_C_smoke.out 2>> $OUT/pr56_C.err; echo "smoke rc=$?")
tail -n 3 $OUT/pr56_C_smoke.out | cut -c1-9000
