#!/bin/bash
# call G: two sets of six of the new cell from the FINAL tree as git would
# commit it (`git archive $(git write-tree)` under .scratch/pr57_final),
# every run with a seed of its own; then the second half of the sabotage
# table (the working tree)
mkdir -p chiprun_out/pr57
HERE=$(pwd)
CELL=qwen3-next-80b-a3b-d12.serve.longchat
cd .scratch/pr57_final
: > "$HERE/chiprun_out/pr57/G_final.out"
for seed in 2157100019 2157100037 1157100049 3157100051 2157100063 957100079 2157200011 2157200023 1257200039 3057200041 2157200057 857200069; do
  python3 benchmarks/run.py --workload $CELL --seed $seed --seconds 50 --trace 0 2> "$HERE/chiprun_out/pr57/G_final_$seed.err" | sed "s|^|final $CELL seed=$seed |" >> "$HERE/chiprun_out/pr57/G_final.out"
  tail -n 1 "$HERE/chiprun_out/pr57/G_final.out" | cut -c1-700
done
cd "$HERE"
python3 bench_artifacts/pr57/sabotage.py --seconds 20 --only e_state_not_carried_across_prefill_chunks,f_seated_slot_keeps_its_last_tenants_state,g_output_gate_dropped,h_rotary_over_all_256,i_shared_experts_gate_dropped,j_elsewhere_computed_by_e_mod_64 > chiprun_out/pr57/G_sabotage.out 2> chiprun_out/pr57/G_sabotage.err
echo "sabotage rc=$?"; grep "^{" chiprun_out/pr57/G_sabotage.out | cut -c1-600
