"""A prefill chunk's attention over a learned selection alone, on the
chip, at `glm-5.2-d5.serve.longctx`'s shapes (one request's 512 queries
of 64 heads, 192 + 64 key values and 256 value values a head, latent
rows of 512 + 64 in a pool of 12,289 blocks of 16 rows of 640 lanes, a
table of 1,536 blocks, tiles of 1,024 positions, 2,048 rows chosen a
query): `serving/sparse.py::attend_tiles` (the parent's way, and the
kernel's oracle) and `kernels/masked_latent.py`'s call, each walking 4,
8 and 16 tiles.

    chiprun -- python bench_artifacts/pr60/probe.py

Times are DEVICE times from a profiler trace of 6 runs a case
(`benchmarks/trace_reduce.py`): a run of the jitted program (median),
and the device seconds of its operations by name, a run.  The kernel's
line holds the Mosaic call and, apart, what `jax.numpy` lays out before
it.  Lines go to `chiprun_out/pr60/probe.jsonl`.
"""
import json
import os
import shutil
import statistics
import sys
import types

sys.path.insert(0, os.getcwd())

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import trace_reduce
from deepspeed_tpu.kernels import masked_latent
from deepspeed_tpu.serving import sparse

T, H, NOPE, ROPE, V, RANK = 512, 64, 192, 64, 256, 512
BS, NBLOCKS, WIDTH, TILE, TOPK = 16, 12289, 1536, 1024, 2048
RUNS = 6
CFG = types.SimpleNamespace(kv_lora_rank=RANK, v_head_dim=V,
                            qk_rope_head_dim=ROPE, head_dim=NOPE + ROPE,
                            yarn=None)
SCHED = types.SimpleNamespace(block_size=BS)


def operands(seed=60):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = jax.random.normal(ks[0], (NBLOCKS * BS, 640), jnp.bfloat16)
    kv_b = jax.random.normal(ks[1], (RANK, H * (NOPE + V)),
                             jnp.bfloat16) * RANK ** -.5
    q_nope = jax.random.normal(ks[2], (1, T, H, NOPE), jnp.bfloat16)
    q_rope = jax.random.normal(ks[3], (1, T, H, ROPE), jnp.bfloat16)
    table = jax.random.permutation(ks[4], NBLOCKS - 1)[:WIDTH] + 1
    return kv_b, q_nope, q_rope, pool, table[None].astype(jnp.int32)


def selection(n_tiles, seed):
    """The chunk's last query at the last position of tile `n_tiles`;
    every query's `TOPK` rows drawn among those it sees."""
    rng = np.random.RandomState(seed)
    q_pos = n_tiles * TILE - T + np.arange(T)
    score = rng.rand(T, WIDTH * BS).astype(np.float32)
    score[np.arange(WIDTH * BS)[None, :] > q_pos[:, None]] = -1.0
    kth = np.partition(score, -TOPK, axis=1)[:, -TOPK]
    return jnp.asarray((score >= kth[:, None]) & (score >= 0))[None]


def oracle(kv_b, q_nope, q_rope, pool, tables, mask, n_tiles):
    return sparse.attend_tiles(CFG, kv_b, q_nope, q_rope, pool, tables, mask,
                               n_tiles, SCHED, TILE)


def kernel(kv_b, q_nope, q_rope, pool, tables, mask, n_tiles):
    return masked_latent.masked_latent_attention_pallas(
        CFG, kv_b, q_nope, q_rope, pool, tables, mask, n_tiles, SCHED, TILE)


def traced(fn, args, tag):
    """(median device ms of a run, {operation: device ms a run})."""
    run = jax.jit(fn)
    out = jax.block_until_ready(run(*args))
    logdir = f"/tmp/pr60_probe/{tag}"
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    for _ in range(RUNS):
        jax.block_until_ready(run(*args))
    jax.profiler.stop_trace()
    path, = [os.path.join(d, f) for d, _, fs in os.walk(logdir) for f in fs
             if f.endswith(".xplane.pb")]
    trace = trace_reduce.reduce_file(path)
    runs = trace.module_durations(f"jit_{fn.__name__}")
    ops = {k: v / RUNS * 1e3 for k, v in trace.op_seconds()[:14]}
    return out, statistics.median(runs) * 1e3, ops


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    ops = operands()
    lines = []
    for n in (4, 8, 16):
        mask = selection(n, seed=n)
        args = (*ops, mask, jnp.int32(n))
        line = {"probe": "masked_tile_walk", "tiles": n, "queries": T,
                "heads": H, "rows_chosen": int(mask[0, -1].sum()),
                "device": jax.devices()[0].device_kind}
        outs = {}
        for fn in (oracle, kernel):
            outs[fn.__name__], ms, by_op = traced(fn, args,
                                                  f"{fn.__name__}_{n}")
            line[f"{fn.__name__}_ms"] = ms
            line[f"{fn.__name__}_ms_a_tile"] = ms / n
            line[f"{fn.__name__}_ops_ms"] = by_op
        call = [v for k, v in line["kernel_ops_ms"].items()
                if k.startswith("custom-call")]
        line["kernel_call_ms_a_tile"] = sum(call) / n
        line["kernel_laid_out_ms"] = line["kernel_ms"] - sum(call)
        line["ref_max"] = float(jnp.max(jnp.abs(outs["oracle"])))
        line["max_abs_err"] = float(jnp.max(jnp.abs(
            outs["kernel"] - outs["oracle"])))
        print(json.dumps(line), flush=True)
        lines.append(line)
    os.makedirs("chiprun_out/pr60", exist_ok=True)
    with open("chiprun_out/pr60/probe.jsonl", "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
