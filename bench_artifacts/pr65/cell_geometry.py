"""What `ServeEngine.__init__` lays out for every serving cell of
BENCHMARK.json, at the cell's own size, without a chip and without a
byte of it allocated: the engine is built under `jax.eval_shape` from
the shapes of the cell's weights, and what is read back is the schedule
it hands the program builder (every field the compiled programs depend
on), every layer's entry of `caches` (shapes and dtypes), the width of
a slot's row of tables, and what the cache says of itself.  The same
script runs in the parent's checkout and in the change's; PR 65 moves
the arithmetic from the engine's constructor to `kv_cache.cache_plan`,
so the two files must be equal — with `stablehlo_sha.py` (same schedule
and same entries in, same StableHLO out) that is "no compiled program
changes".

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python \
        <checkout>/bench_artifacts/pr65/cell_geometry.py <checkout> out.json
"""

import json
import os
import sys

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
os.chdir(root)

import jax  # noqa: E402

from benchmarks import harness  # noqa: E402
from deepspeed_tpu.serving import ServeConfig, ServeEngine  # noqa: E402


def probe(name, w, config, family):
    serve = ServeConfig(**w["serve"])
    model = family.build(config, seq_len=serve.max_seq_len, n_dev=1,
                         **w.get("model", {}))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    out = {}

    def build(params):
        eng = ServeEngine(model, params, serve)
        kv = eng.kv
        out["schedule"] = list(eng.programs["schedule"])
        out["tables"] = list(eng._slots.host["tables"].shape)
        out["describe"] = kv.describe()
        out["capacity"] = [kv.capacity_blocks, kv.token_capacity,
                           kv.blocks_needed(1), kv.blocks_needed(4097),
                           kv.blocks_needed(serve.max_seq_len)]
        return kv.caches

    caches = jax.eval_shape(build, shapes)
    out["entries"] = [[list(a.shape) + [str(a.dtype)]
                       for a in jax.tree_util.tree_leaves(e)]
                      for e in caches]
    return out


if __name__ == "__main__":
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    result = {}
    for c in bench["workloads"]:
        if ".serve." not in c["name"]:
            continue
        w = harness.load_json("workloads", c["name"] + ".json")
        config = harness.load_json("configs", c["config"] + ".json")
        family = harness.plugin("models", config["family"])
        result[c["name"]] = probe(c["name"], w, config, family)
        print(c["name"], result[c["name"]]["schedule"], flush=True)
    with open(sys.argv[2], "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
