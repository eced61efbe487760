#!/bin/bash
# PR 54 call B: prefill_chunk 1024 against call A's 512 (traced, the same
# deal at 0.3 requests/s), then what the check reads under two seeded
# scales (sound, the fp8 control and three broken mechanisms each).
set -x
mkdir -p chiprun_out/pr54
W=benchmarks/workloads/glm-5.2-d5.serve.longctx.json
sed -i 's/"prefill_chunk": 512/"prefill_chunk": 1024/' $W
python3 benchmarks/run.py --workload glm-5.2-d5.serve.longctx --seed 2254000017 --seconds 50 --trace 1 \
    > chiprun_out/pr54/B_traced1024.out 2> chiprun_out/pr54/B_traced1024.err; echo "rc=$?"
tail -c 4500 chiprun_out/pr54/B_traced1024.out
sed -i 's/"prefill_chunk": 1024/"prefill_chunk": 512/' $W
python3 bench_artifacts/pr54/sabotage.py --seconds 20 --seed 2254000019 --init embed_std=1.0,query_std=0.05 \
    --only none,h_products_and_rows_at_fp8_e4m3,a_indexer_bypassed_every_row_attended,d_1024_rows_chosen_for_2048,e_selection_bias_let_into_the_weights \
    > chiprun_out/pr54/B_init_e1_q05.out 2> chiprun_out/pr54/B_init_e1_q05.err
grep sabotage chiprun_out/pr54/B_init_e1_q05.out
python3 bench_artifacts/pr54/sabotage.py --seconds 20 --seed 2254000023 --init embed_std=1.0,query_std=0.035 \
    --only none,h_products_and_rows_at_fp8_e4m3,d_1024_rows_chosen_for_2048 \
    > chiprun_out/pr54/B_init_e1_q035.out 2> chiprun_out/pr54/B_init_e1_q035.err
grep sabotage chiprun_out/pr54/B_init_e1_q035.out
tail -5 chiprun_out/pr54/B_init_e1_q035.err
