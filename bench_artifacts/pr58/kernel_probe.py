"""A prefill chunk's routed product alone, on the chip, at the four MoE
cells' shapes: `experts_grouped` (the parent's way: `lax.ragged_dot`
over all T x top_k rows), `experts_slabs` with XLA's grouped products
over the slab, and `experts_slabs` with the `grouped_experts` kernel;
the kernel alone against `grouped_ffn` alone over one slab; the worst
difference from `experts_grouped`; and the overflow case — every
assignment held, so several slabs — against the same oracle.

    chiprun -- python bench_artifacts/pr58/kernel_probe.py

A timed program chains 12 layers (2 sets of weights in turn), 10
programs are enqueued and the last waited for; ms are of one layer.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.kernels import moe_kernels
from deepspeed_tpu.kernels.registry import kernel_config
from deepspeed_tpu.moe import dropless

LAYERS, SETS, HBM = 12, 2, 819e9

# (cell, tokens, top_k, experts held, experts in all, D, F)
CASES = [
    ("longchat", 512, 10, 64, 512, 2048, 512),
    ("mixedlen", 512, 8, 16, 128, 4096, 4096),
    ("longctx", 512, 8, 16, 256, 6144, 2048),
    ("chatgen", 512, 6, 64, 64, 2048, 1408),
]


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3 / LAYERS


def chained(one):
    """12 layers of `one(x, experts)` -> x, the values kept bounded."""
    @jax.jit
    def run(x, weights):
        for i in range(LAYERS):
            x = jnp.tanh(one(x, weights[i % SETS]))
        return x
    return run


def make(case, seed=58):
    _, T, k, E, total, D, F = case
    key = jax.random.PRNGKey(seed)
    weights = []
    for _ in range(SETS):
        k1, k2, k3, key = jax.random.split(key, 4)
        weights.append({
            "gate": jax.random.normal(k1, (E, D, F), jnp.bfloat16) * D ** -.5,
            "up": jax.random.normal(k2, (E, D, F), jnp.bfloat16) * D ** -.5,
            "down": jax.random.normal(k3, (E, F, D), jnp.bfloat16) * F ** -.5})
    k1, k2, key = jax.random.split(key, 3)
    x = jax.random.normal(k1, (T, D), jnp.float32)
    w, idx = dropless.route(x, jax.random.normal(k2, (D, total)), k,
                            renormalize=True)
    return x, weights, w, idx


def probe(case):
    name, T, k, E, total, D, F = case
    x, weights, w, idx = make(case)
    held = None
    if total != E:
        w, idx, held = dropless.held_assignments(w, idx, 0, E)
    C = dropless.slab_rows(T, k, E, total)
    mb = 3 * E * D * F * 2 / 1e6
    line = {"probe": "routed_product", "cell": name, "tokens": T,
            "top_k": k, "held": E, "total": total, "model_dim": D,
            "expert_dim": F, "rows": T * k, "slab_rows": C,
            "rows_held": int(T * k if held is None else held.sum()),
            "weights_mb": mb, "device": jax.devices()[0].device_kind}

    ways = {
        "grouped": (lambda x, ex: dropless.experts_grouped(
            x, ex, w, idx, held), "jnp"),
        "slabs_ragged": (lambda x, ex: dropless.experts_slabs(
            x, ex, w, idx, total, held), "jnp"),
        "slabs_kernel": (lambda x, ex: dropless.experts_slabs(
            x, ex, w, idx, total, held), "pallas"),
    }
    outs = {}
    for way, (one, impl) in ways.items():
        with kernel_config(ops={"grouped_experts": impl}):
            t0 = time.perf_counter()
            outs[way] = jax.block_until_ready(
                jax.jit(one)(x, weights[0]))
            line[f"{way}_first_call_s"] = time.perf_counter() - t0
            ms = timed(chained(one), x, weights)
        line[f"{way}_ms"] = ms
        line[f"{way}_stream_pct"] = mb * 1e6 / HBM / (ms * 1e-3) * 100
    ref = outs["grouped"]
    line["ref_max"] = float(jnp.max(jnp.abs(ref)))
    for way in ("slabs_ragged", "slabs_kernel"):
        line[f"{way}_max_abs_err"] = float(jnp.max(jnp.abs(outs[way] - ref)))

    # the product over one slab alone: the kernel and XLA's
    offsets = jnp.clip(dropless._by_expert(idx, E, held)[1], 0, C)
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, D), jnp.bfloat16)
    for way, fn in (("kernel_alone", moe_kernels.grouped_experts_pallas),
                    ("ragged_alone", dropless.grouped_ffn)):
        run = chained(lambda xs, ex: fn(xs, ex, offsets).astype(xs.dtype))
        ms = timed(run, xs, weights)
        line[f"{way}_ms"] = ms
        line[f"{way}_stream_pct"] = mb * 1e6 / HBM / (ms * 1e-3) * 100
    print(json.dumps(line), flush=True)
    return line


def overflow(case):
    """Every assignment held by this chip: `T * top_k / C` slabs."""
    name, T, k, E, total, D, F = case
    x, weights, w, idx = make(case, seed=59)
    w, idx, held = dropless.held_assignments(w, idx % E, 0, E)
    slabs, C = dropless.slabs_walked(idx, weights[0], total, held, None)
    ref = jax.jit(dropless.experts_grouped)(x, weights[0], w, idx, held)
    with kernel_config(ops={"grouped_experts": "pallas"}):
        fn = jax.jit(lambda x, ex: dropless.experts_slabs(
            x, ex, w, idx, total, held))
        got = fn(x, weights[0])
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(x, weights[0])
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / 10 * 1e3
    line = {"probe": "overflow", "cell": name, "rows_held": int(held.sum()),
            "slab_rows": C, "slabs": int(slabs), "ms": ms,
            "max_abs_err": float(jnp.max(jnp.abs(got - ref))),
            "ref_max": float(jnp.max(jnp.abs(ref)))}
    print(json.dumps(line), flush=True)
    return line


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    lines = [probe(case) for case in CASES]
    lines += [overflow(CASES[0]), overflow(CASES[1])]
    os.makedirs("chiprun_out/pr58", exist_ok=True)
    with open("chiprun_out/pr58/kernel_probe.jsonl", "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
