#!/bin/bash
# PR 54 call C: the sweep (prefill_chunk 512, the final seeded scales),
# then the cell's check against every broken mechanism, 20 s a run.
set -x
mkdir -p chiprun_out/pr54
python3 benchmarks/sweep.py --workload glm-5.2-d5.serve.longctx --rates 0.4,0.5,0.6,0.7,0.8 --seconds 50 \
    > chiprun_out/pr54/C_sweep.out 2> chiprun_out/pr54/C_sweep.err; echo "rc=$?"
grep rate_rps chiprun_out/pr54/C_sweep.out | cut -c1-900
python3 bench_artifacts/pr54/sabotage.py --seconds 20 --seed 2254000029 --rate 0.4 \
    > chiprun_out/pr54/C_sabotage.out 2> chiprun_out/pr54/C_sabotage.err
grep sabotage chiprun_out/pr54/C_sabotage.out | cut -c1-700
tail -3 chiprun_out/pr54/C_sabotage.err
