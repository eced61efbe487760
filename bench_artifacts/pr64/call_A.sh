# PR 64, call A: chip_smoke's kernels phase (a sliding layer's prefill
# chunk at the cell's shapes against the gather), the probe of one
# layer's chunk (walk, gather, the full layer's walk), then
# `command-a-plus-d4.serve.mixedlen`, parent (0f6573c under
# .scratch/parent) against the working tree: a traced pair and one pair
# with the profiler off.
set -x
mkdir -p chiprun_out
python3 bench_artifacts/pr64/kernels_only.py 2>chiprun_out/pr64_A_kernels.err | tee chiprun_out/pr64_A_kernels.out | python3 -c "
import sys, json
for ln in sys.stdin:
    try: d = json.loads(ln)
    except ValueError: continue
    if 'kernels' in d:
        for k in d['kernels']: print(k['kernel'], k.get('max_abs_err'))
    else: print(str(d)[:300])
"
tail -c 400 chiprun_out/pr64_A_kernels.err
python3 bench_artifacts/pr64/chunk_probe.py 2>chiprun_out/pr64_A_probe.err | tee chiprun_out/pr64_A_probe.out
tail -c 300 chiprun_out/pr64_A_probe.err
TAG=A TRACE_SEED=2164000117 SEEDS="2164100129" sh bench_artifacts/pr64/call_pairs.sh
