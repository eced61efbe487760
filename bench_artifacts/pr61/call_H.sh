#!/bin/bash
# PR 61, call H: chip_smoke.py from the final tree (git archive under
# .scratch/final), then other serving cells, parent against final, a
# pair a cell sharing its seed (call_D.sh)
OUT=$PWD/chiprun_out/pr61; mkdir -p $OUT
if [ "$1" = "smoke" ]; then shift
  (cd .scratch/final && timeout 1500 python3 chip_smoke.py > $OUT/E_chip_smoke.out 2> $OUT/E_chip_smoke.err); echo "smoke rc=$?"; tail -2 $OUT/E_chip_smoke.out | cut -c1-400
fi
bash bench_artifacts/pr61/call_D.sh 0 "$@"
