#!/bin/bash
# Call E (PR 46): the controls whose verdict the final limits decide
# (logit_margin 0.0025, top1_agreement_floor 0.91, set after call C's six
# runs read a sound worst gap of 0.0017), from the final tree.
set -x
OUT=$PWD/chiprun_out/pr46; mkdir -p $OUT
cd .scratch/final
python3 bench_artifacts/pr46/sabotage.py --seconds 25 \
  --only h_product_inputs_rounded_to_fp8_e4m3,none,a1_state_not_carried_from_chunk_to_chunk,d_seated_slot_keeps_its_last_tenants_state,e_attention_scale_one_eighth,b1_conv_rows_dropped_from_chunk_to_chunk \
  > $OUT/E_sabotage.out 2> $OUT/E_sabotage.err; echo "sabotage rc=$?"
cat $OUT/E_sabotage.out | cut -c1-700
