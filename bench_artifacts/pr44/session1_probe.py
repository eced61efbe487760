"""Builder's probe: the benchmark's own command, several times in one
process, with the cell's rate and prefill chunk overridden.
  python .scratch/probe.py <cell> chunk:rate:trace[:seed] ..."""
import gc, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks import run

orig = run.load_json
over = {}


def load(*parts):
    d = orig(*parts)
    if parts[0] == "workloads" and over.get("chunk"):
        d["serve"]["prefill_chunk"] = over["chunk"]
    if parts[0] == "traffic" and over.get("rate"):
        d["rate_rps"] = over["rate"]
    return d


run.load_json = load
cell = sys.argv[1]
for i, spec in enumerate(sys.argv[2:]):
    f = spec.split(":")
    over.update(chunk=int(f[0]), rate=float(f[1]))
    seed = f[3] if len(f) > 3 else str(2147480000 + 7919 * i)
    print(f"## probe chunk={f[0]} rate={f[1]} trace={f[2]} seed={seed}", flush=True)
    run.main(["--workload", cell, "--seed", seed, "--seconds", "50", "--trace", f[2]])
    gc.collect()
