#!/bin/bash
# call E: the finer sweep on the repaired tree, then the first half of the
# sabotage table through the cell's own check
mkdir -p chiprun_out/pr57
python3 benchmarks/sweep.py --workload qwen3-next-80b-a3b-d12.serve.longchat --rates 1.6,1.8,2.0,2.2 --seconds 50 > chiprun_out/pr57/E_sweep.out 2> chiprun_out/pr57/E_sweep.err
echo "sweep rc=$?"; grep "^{" chiprun_out/pr57/E_sweep.out | cut -c1-1100
python3 bench_artifacts/pr57/sabotage.py --seconds 20 --only none,k_products_rows_and_rule_inputs_at_fp8_e4m3,a_delta_term_dropped_plain_linear_attention,b_beta_is_one,c_g_is_zero_no_decay,d_qk_l2_norm_dropped > chiprun_out/pr57/E_sabotage.out 2> chiprun_out/pr57/E_sabotage.err
echo "sabotage rc=$?"; grep "^{" chiprun_out/pr57/E_sabotage.out | cut -c1-700; tail -2 chiprun_out/pr57/E_sabotage.err | cut -c1-300
