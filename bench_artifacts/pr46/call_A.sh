#!/bin/bash
# Call A (PR 46): the new cell on the parent (must fail at once), one old
# cell traced on the parent under this PR's benchmark files, the new cell
# traced on the change, then a first sweep.
set -x
OUT=chiprun_out/pr46; mkdir -p $OUT
CELL=granite-4.0-h-micro.serve.chatrate
( cd .scratch/parent && time python3 benchmarks/run.py --workload $CELL --seed 2190000101 --seconds 50 --trace 0 \
    > ../../$OUT/A_parent_newcell.out 2> ../../$OUT/A_parent_newcell.err; echo "parent new cell rc=$?" )
tail -5 $OUT/A_parent_newcell.err
( cd .scratch/parent && python3 benchmarks/run.py --workload gpt2-xl.serve.chat --seed 2190000102 --seconds 50 --trace 1 \
    > ../../$OUT/A_parent_chat_traced.out 2> ../../$OUT/A_parent_chat_traced.err; echo "parent chat traced rc=$?" )
tail -1 $OUT/A_parent_chat_traced.out | cut -c1-1500
python3 benchmarks/run.py --workload $CELL --seed 2190000103 --seconds 50 --trace 1 \
    > $OUT/A_newcell_traced.out 2> $OUT/A_newcell_traced.err; echo "new cell traced rc=$?"
grep -v "^\[" $OUT/A_newcell_traced.err | tail -15
cat $OUT/A_newcell_traced.out | cut -c1-6000
python3 bench_artifacts/pr46/probe.py --rates 3,5,7,9 --seconds 50 > $OUT/A_sweep.out 2> $OUT/A_sweep.err; echo "sweep rc=$?"
grep -v "^\[" $OUT/A_sweep.err | tail -8
cat $OUT/A_sweep.out
