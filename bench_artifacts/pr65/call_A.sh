# PR 65, call A: the tree git would commit (`git archive $(git write-tree)`
# under .scratch/f65, the tree named in final_tree_of_call_A.txt) —
# `chip_smoke.py` whole from it, then, against the parent (4a8262e under
# .scratch/p65), the three cells whose cache does the most at a step
# boundary: the ring (`mixedlen`), arrays by slot under 64 lanes
# (`chatrate`), the window and its summary rows (`longdoc`).
mkdir -p chiprun_out
(cd .scratch/f65 && python chip_smoke.py > $OLDPWD/chiprun_out/pr65_A_smoke.out 2> $OLDPWD/chiprun_out/pr65_A_smoke.err; echo "chip_smoke rc=$?"; tail -n 1 $OLDPWD/chiprun_out/pr65_A_smoke.out | cut -c1-300)
date
CHANGE=$PWD/.scratch/f65 TAG=A CELLS="command-a-plus-d4.serve.mixedlen granite-4.0-h-micro.serve.chatrate evabyte-d16.serve.longdoc" SEEDS="2165100137 1165100151 3065200163 865200167 1265300179 765300191" sh bench_artifacts/pr65/call_pairs.sh
