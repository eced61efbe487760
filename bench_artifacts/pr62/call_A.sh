# PR 62, call A: chip_smoke's kernels phase (the sliding walk at the
# cell's tile against the gather), then `command-a-plus-d4.serve.mixedlen`,
# parent (a7eb133 under .scratch/parent) against the working tree: a
# traced pair and one pair with the profiler off.
set -x
python3 bench_artifacts/pr62/kernels_only.py 2>chiprun_out/pr62_A_kernels.err | tee chiprun_out/pr62_A_kernels.out | python3 -c "
import sys, json
for ln in sys.stdin:
    try: d = json.loads(ln)
    except ValueError: continue
    if 'kernels' in d:
        for k in d['kernels']: print(k['kernel'], k.get('max_abs_err'))
    else: print(str(d)[:300])
"
tail -c 400 chiprun_out/pr62_A_kernels.err
TAG=A TRACE_SEED=2162000113 SEEDS="2162100127" sh bench_artifacts/pr62/call_pairs.sh
