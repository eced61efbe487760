# PR 58, chip call C: the FINAL tree from `git archive $(git write-tree)`
# (unpacked under .scratch/pr58_final) — `chip_smoke.py` from it, whose
# kernels phase holds the slab walk to XLA's grouped products at the
# longchat and mixedlen shapes, then the claimed cell: a traced pair and
# four pairs against the parent (call_pairs.sh with CHANGE set).
set -x
OUT=$PWD/chiprun_out; mkdir -p $OUT
(cd .scratch/pr58_final && python3 chip_smoke.py > $OUT/pr58_C_smoke.out 2>> $OUT/pr58_C.err; echo "smoke rc=$?")
tail -n 3 $OUT/pr58_C_smoke.out | cut -c1-6000
CHANGE=$PWD/.scratch/pr58_final TAG=C TRACE_SEED=2158400129 SEEDS="2158410239 2158420343 2158430453 2158440563" FIRST=parent bash bench_artifacts/pr58/call_pairs.sh
