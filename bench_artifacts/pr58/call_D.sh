# PR 58, chip call D: `setup_s` COLD — each run with an empty compile
# cache of its own (JAX_COMPILATION_CACHE_DIR) — in the claimed cell and
# in mixedlen, parent and the final tree, the profiler off.
set -x
ROOT=$PWD; mkdir -p chiprun_out
i=0
for CELL in qwen3-next-80b-a3b-d12.serve.longchat command-a-plus-d4.serve.mixedlen; do
  for side in parent final; do
    i=$((i+1)); s=$((2158500100 + i))
    (cd .scratch/pr58_$side && JAX_COMPILATION_CACHE_DIR=$ROOT/.scratch/cold_$i python3 benchmarks/run.py --workload $CELL --seed $s --seconds 50 --trace 0 2>> $ROOT/chiprun_out/pr58_D.err | tee -a $ROOT/chiprun_out/pr58_D.full | grep "^{" | sed "s|^|${side/final/change} $CELL seed=$s trace=0 |" | tee -a $ROOT/chiprun_out/pr58_D.out | cut -c1-700)
    grep "^# {" $ROOT/chiprun_out/pr58_D.full | tail -n 1 | sed "s|^|${side/final/change} $CELL seed=$s trace=0 |" >> $ROOT/chiprun_out/pr58_D.check
  done
done
