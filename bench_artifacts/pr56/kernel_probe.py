"""The prefill walk alone, on the chip, at `mixedlen`'s full layer's
shapes (one request, a chunk of 512 queries of 128 heads on 8 K/V heads
of 128, 16,385 blocks of 16 bf16 rows, a table of 1,024 entries): device
time of a chunk at three positions of a prompt for several tiles
(`--tiles tq:rows,...`; the first is what `prefill_tiles` picks), against
the oracle's gather of the whole table, and the worst difference from it.

    chiprun -- python bench_artifacts/pr56/kernel_probe.py

Times are 20 calls enqueued and the last one waited for, over 20.
"""
import argparse
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.kernels import paged
from deepspeed_tpu.serving.layers import grouped_attention_reference

T, H, KV, DH, BS, W, NB = 512, 128, 8, 128, 16, 1024, 16385
ARGS = dict(kv_heads=KV, block_size=BS, scale=None)


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="64:512,32:512,64:256,32:256,16:512")
    ap.add_argument("--starts", default="0,3584,15872")
    a = ap.parse_args()
    assert jax.default_backend() == "tpu", jax.default_backend()
    rng = np.random.RandomState(56)
    pool = lambda: jnp.asarray(rng.randn(NB * BS, KV * DH) * 0.5,
                               jnp.bfloat16)
    ck, cv = pool(), pool()
    q = jnp.asarray(rng.randn(1, T, H, DH), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, NB))[:W][None],
                         jnp.int32)
    oracle = jax.jit(lambda *x: grouped_attention_reference(*x, **ARGS))
    walk = lambda *x: paged.grouped_attention_pallas(*x, **ARGS)
    lines = []
    for start in map(int, a.starts.split(",")):
        q_pos = jnp.asarray(start + np.arange(T)[None], jnp.int32)
        ref = oracle(q, ck, cv, tables, q_pos)
        line = {"start": start, "rows": start + T,
                "oracle_ms": timed(oracle, q, ck, cv, tables, q_pos)}
        for tile in a.tiles.split(","):
            tq, rows = map(int, tile.split(":"))
            paged._PREFILL_QUERIES, paged._PREFILL_TILE_ROWS = (tq,), rows
            jax.clear_caches()
            t0 = time.perf_counter()
            out = jax.block_until_ready(walk(q, ck, cv, tables, q_pos))
            first = time.perf_counter() - t0
            ms = timed(walk, q, ck, cv, tables, q_pos)
            flops = 4 * H * DH * (T * start + T * (T + 1) // 2)  # causal
            line[tile] = {
                "ms": ms, "first_call_s": first,
                "max_abs_err": float(jnp.max(jnp.abs(out - ref))),
                "ref_max": float(jnp.max(jnp.abs(ref))),
                "mxu_share_pct": flops / (ms * 1e-3) / 197e12 * 100}
        print(json.dumps(line), flush=True)
        lines.append(line)
    os.makedirs("chiprun_out/pr56", exist_ok=True)
    with open("chiprun_out/pr56/kernel_probe.jsonl", "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
