"""Times, on the chip, the ways to find the 2,048 largest of a query's
index scores at the cell's shapes: a decode step's [8, 24576] and a
prefill chunk's [512, 24576].  A builder's script (PR 54):

    python3 bench_artifacts/pr54/select_probe.py
"""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.generation import kth_largest
from deepspeed_tpu.models.glm_moe_dsa import select_mask

K, L = 2048, 24576


def kth_by_sort(x, k):
    return jnp.take_along_axis(jnp.sort(x, axis=-1),
                               (x.shape[-1] - k)[:, None], axis=-1)[:, 0]


def timed(name, fn, *args, n=10):
    f = jax.jit(fn)
    out = jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    print(json.dumps({"probe": name, "ms": 1e3 * (time.perf_counter() - t0)
                      / n, "device": jax.devices()[0].device_kind}),
          flush=True)


def main():
    key = jax.random.PRNGKey(0)
    for rows in (8, 512):
        x = jax.random.normal(key, (rows, L), jnp.float32)
        seen = jnp.ones((rows, L), bool)
        k = jnp.full((rows,), K, jnp.int32)
        timed(f"top_k[{rows}]", lambda a: jax.lax.top_k(a, K), x)
        timed(f"sort_key_val[{rows}]", lambda a: jax.lax.sort_key_val(
            -a, jnp.broadcast_to(jnp.arange(L), a.shape))[1][:, :K], x)
        timed(f"kth_32_passes[{rows}]", kth_largest, x, k)
        timed(f"kth_by_full_sort[{rows}]", kth_by_sort, x, k)
        timed(f"select_mask_32_passes[{rows}]", lambda a, s: select_mask(
            a, s, K), x, seen)
        # call A also timed select_mask with its threshold from
        # `lax.top_k` (A_select_probe.out); select_mask has had one way
        # to its threshold since the review
        timed(f"cumsum[{rows}]", lambda a: jnp.cumsum(
            a > 0, axis=-1, dtype=jnp.int32), x)
    x = jax.random.normal(key, (128, L), jnp.float32)
    timed("reference_top_k_scatter[128]", lambda a: jnp.zeros(
        a.shape, bool).at[jnp.arange(128)[:, None],
                          jax.lax.top_k(a, K)[1]].set(True), x)
    # the row gather of a decode step: 8 x 2,048 rows of 640 lanes
    pool = jnp.zeros((12289 * 16, 640), jnp.bfloat16)
    at = jax.random.randint(key, (8, K), 0, 12289 * 16)
    timed("gather_rows[8x2048]", lambda p, i: p[i], pool, at)


if __name__ == "__main__":
    main()
