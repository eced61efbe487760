"""PR 45's micro-benchmark (chip only): the routed product of a decode
step at the two MoE cells' shapes, three ways — `experts_masked` (every
held expert), the `touched_experts` kernel at several column tiles, and
the plain-XLA `lax.while_loop` over the touched list — with the cells'
touched counts.  A reading is the device's time for ONE product: a
jitted chain of 9 products, each fed the one before, less a chain of 1,
over 8 (medians of 20 calls after 3 warm ones; the host's launch and
read-back cancel).  Prints one JSON line a reading, with the GB the way
must stream and its share of 819 GB/s.

    chiprun -- python bench_artifacts/pr45/kernel_probe.py
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from deepspeed_tpu.kernels import moe_kernels  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402

assert jax.default_backend() == "tpu", jax.default_backend()
PEAK = 819e9


def _median_ms(fn, *args, n=20):
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return float(np.median(ts)) * 1e3


def timed(fn, x, *rest):
    """Device ms of one `fn(x, *rest)`: chains of 9 and of 1."""
    def chain(reps):
        def run(x, *rest):
            def body(_, x):
                return x + 1e-3 * fn(x, *rest).astype(x.dtype)
            return jax.lax.fori_loop(0, reps, body, x)
        return jax.jit(run)
    return (_median_ms(chain(9), x, *rest)
            - _median_ms(chain(1), x, *rest)) / 8


def while_loop_form(x, experts, w, ids, n):
    """The touched list walked in plain XLA: an expert's matrices
    sliced in the loop."""
    dt = experts["gate"].dtype
    xb = x.astype(dt)

    def body(c):
        j, acc = c
        e = ids[j]
        pick = lambda m: jax.lax.dynamic_index_in_dim(m, e, 0, False)
        g = jnp.dot(xb, pick(experts["gate"]),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(xb, pick(experts["up"]),
                    preferred_element_type=jnp.float32)
        out = jnp.dot((jax.nn.silu(g) * u).astype(dt), pick(experts["down"]),
                      preferred_element_type=jnp.float32)
        col = jax.lax.dynamic_index_in_dim(w, e, 1, True)
        return j + 1, acc + out * col

    return jax.lax.while_loop(lambda c: c[0] < n, body,
                              (jnp.int32(0), jnp.zeros(x.shape, jnp.float32))
                              )[1]


def scene(T, E, D, F, k, live_rows, held_share, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    mk = lambda key, shape: (jax.random.normal(key, shape, jnp.float32)
                             * 0.02).astype(jnp.bfloat16)
    experts = {"gate": mk(ks[0], (E, D, F)), "up": mk(ks[1], (E, D, F)),
               "down": mk(ks[2], (E, F, D))}
    x = jax.random.normal(ks[3], (T, D), jnp.float32)
    total = E * held_share
    router = jax.random.normal(ks[4], (D, total), jnp.float32) * 0.02
    weights, idx = dropless.route(x, router, k)
    held = None
    if held_share > 1:
        weights, idx, held = dropless.held_assignments(weights, idx, 0, E)
    live = jnp.arange(T) < live_rows
    return experts, x, weights, idx, held, live


def main():
    out = []
    for name, (T, E, D, F, k, live_rows, share) in {
            "chatgen": (32, 64, 2048, 1408, 6, 12, 1),
            "mixedlen": (16, 16, 4096, 4096, 8, 9, 8)}.items():
        experts, x, weights, idx, held, live = scene(
            T, E, D, F, k, live_rows, share, 45)
        ids, n = dropless.touched_list(idx, live, E, held)
        w = dropless.combine_weights(
            jnp.where(live[:, None], weights, 0.0), idx, E)
        n_host = int(n)
        per = 3 * D * F * 2
        want = np.asarray(jax.jit(dropless.experts_weighted)(x, experts, w))
        scale = float(np.abs(want).max())

        def report(way, ms, streamed, got=None, **more):
            line = {"cell": name, "way": way, "ms": round(ms, 4),
                    "experts_streamed": streamed, "touched": n_host,
                    "GB": round(streamed * per / 1e9, 4),
                    "bw_share_pct": round(
                        streamed * per / (ms * 1e-3) / PEAK * 100, 1),
                    **more}
            if got is not None:
                line["max_abs_err_over_max"] = float(
                    np.abs(np.asarray(got) - want).max() / scale)
            print(json.dumps(line), flush=True)
            out.append(line)

        report("masked", timed(dropless.experts_weighted, x, experts, w), E)
        report("while_loop", timed(while_loop_form, x, experts, w, ids, n),
               n_host, jax.jit(while_loop_form)(x, experts, w, ids, n))
        for budget_mb in (4, 8, 16, 32, 48, 64, 96):
            moe_kernels._TOUCHED_TILE_BYTES = budget_mb << 20
            tf = moe_kernels.touched_tile(D, F, 2)
            if not tf:
                continue
            fn = lambda x, *a: moe_kernels._touched.__wrapped__(
                x.astype(jnp.bfloat16), *a, interpret=False)
            args = (x, experts["gate"], experts["up"],
                    experts["down"], w, ids, n.reshape(1))
            try:
                ms = timed(fn, *args)
            except Exception as e:  # a tile the chip refuses
                print(json.dumps({"cell": name, "way": "kernel",
                                  "tile": tf, "error": str(e)[:300]}),
                      flush=True)
                continue
            report("kernel", ms, n_host, jax.jit(fn)(*args), tile=tf,
                   budget_mb=budget_mb)
        # every expert touched, and none: the kernel's two ends
        moe_kernels._TOUCHED_TILE_BYTES = 48 << 20
        for label, nn in (("kernel_all", E), ("kernel_none", 0)):
            ids2 = jnp.arange(E, dtype=jnp.int32) * (nn > 0)
            args = (x, experts["gate"], experts["up"],
                    experts["down"], w, ids2, jnp.full((1,), nn, jnp.int32))
            report(label, timed(fn, *args), nn,
                   tile=moe_kernels.touched_tile(D, F, 2))
        del experts
    import os
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pr45_kernel_probe.jsonl", "w") as f:
        for line in out:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
