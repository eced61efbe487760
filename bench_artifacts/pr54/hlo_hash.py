"""sha256 of the StableHLO text (no locations) that `jit_prefill` and
`jit_decode` of three accepted serving cells lower to, at the cells' own
shapes with abstract weights and pools, for the CPU backend (every
attention core the jnp oracle: the Mosaic kernels' files are untouched
by PR 54, and a kernel's serialised body carries its checkout's path,
PERF.md section 7 "Left open by PR 31").  A builder's script (PR 54):

    JAX_PLATFORMS=cpu python3 bench_artifacts/pr54/hlo_hash.py <tree root>

run once on the parent commit's tree and once on the change's; equal
lines mean the programs of those cells are the same programs."""
import hashlib, json, os, sys

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
import jax
import jax.numpy as jnp

from benchmarks.harness import load_json, plugin
from deepspeed_tpu.serving import (PagedKVCache, ServeConfig,
                                   ServeProgramBuilder, ServeSchedule)
from deepspeed_tpu.serving.kv_cache import cache_plan, resolve_kv_dtype

CELLS = ["gpt2-xl.serve.chat", "deepseek-v2-lite-d9.serve.chatgen",
         "command-a-plus-d4.serve.mixedlen"]
benchmark = json.load(open(os.path.join(root, "BENCHMARK.json")))
for name in CELLS:
    entry = next(w for w in benchmark["workloads"] if w["name"] == name)
    w = load_json("workloads", name + ".json")
    config = load_json("configs", entry["config"] + ".json")
    c = ServeConfig(**w["serve"])
    model = plugin("models", config["family"]).build(
        config, seq_len=c.max_seq_len, n_dev=1, **w.get("model", {}))
    cfg, spec = model.config, model.layer_spec()
    plan = cache_plan(spec, cfg, c)
    width, ring = plan.table_width, plan.ring_blocks
    kv_dtype = cfg.param_dtype if c.kv_dtype is None else c.kv_dtype
    sched = ServeSchedule(
        max_batch=c.max_batch, prefill_chunk=c.prefill_chunk,
        block_size=c.block_size, num_blocks=c.num_blocks, table_width=width,
        quantized=c.quant_mode, kv_dtype=resolve_kv_dtype(kv_dtype)[0],
        draft_len=int(c.draft_len), ring_blocks=ring)
    progs = ServeProgramBuilder(model, sched).build()
    caches = jax.eval_shape(lambda: PagedKVCache(
        plan, c.num_blocks, dtype=kv_dtype, prefix_cache=False).caches)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    on = jax.ShapeDtypeStruct
    R, C, W = c.max_batch, c.prefill_chunk, width + ring
    args = {"decode": (on((R,), jnp.int32), on((R,), jnp.int32),
                       on((R,), jnp.bool_), on((R, W), jnp.int32),
                       on((R,), jnp.float32), on((R,), jnp.int32),
                       on((R,), jnp.uint32)),
            "prefill": (on((1, C), jnp.int32), on((), jnp.int32),
                        on((), jnp.int32), on((W,), jnp.int32),
                        on((), jnp.float32), on((), jnp.int32),
                        on((), jnp.uint32))}
    for program in ("prefill", "decode"):
        text = progs[program].lower(params, caches, *args[program]).as_text()
        print(json.dumps({"cell": name, "program": "jit_" + program,
                          "stablehlo_lines": text.count("\n"),
                          "sha256": hashlib.sha256(
                              text.encode()).hexdigest()}), flush=True)
