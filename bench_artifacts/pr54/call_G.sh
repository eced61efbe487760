#!/bin/bash
# PR 54 call G (after the review): the sweep again, on the tree as it is
# committed (selection bias N(0, 0.01), one routed FFN for both blocks,
# logit_margin 1.0 / top1_agreement_floor 0.92): benchmarks/sweep.py,
# 50 s a rate.  0.4 is left out for the chip-minutes: the final tree's
# fourteen runs at 0.48 (call F) are the point below.
set -x
mkdir -p chiprun_out/pr54
python3 benchmarks/sweep.py --workload glm-5.2-d5.serve.longctx --rates 0.5,0.6,0.7,0.8 --seconds 50 \
    > chiprun_out/pr54/G_sweep.out 2> chiprun_out/pr54/G_sweep.err; echo "rc=$?"
grep rate_rps chiprun_out/pr54/G_sweep.out | cut -c1-1100
tail -3 chiprun_out/pr54/G_sweep.err | cut -c1-400
