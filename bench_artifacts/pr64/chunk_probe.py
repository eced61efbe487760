"""Device ms of ONE layer's prefill-chunk attention at mixedlen's shapes
(PR 64, a builder's probe, TPU only): a sliding layer's walk of the
window's live blocks (this PR), the gather of the whole ring it replaces
(`grouped_attention_reference` under `newest`) and the full layer's walk
(PR 56) at the same first position — chains of 9 and 1 calls inside one
program, the difference over 8 (PR 45's lesson: a single call timed from
the host holds ~0.6 ms of launch and read-back).

    python3 bench_artifacts/pr64/chunk_probe.py
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.kernels.paged import grouped_attention_pallas  # noqa: E402
from deepspeed_tpu.serving.kv_cache import pool_rows  # noqa: E402
from deepspeed_tpu.serving.layers import grouped_attention_reference  # noqa: E402
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
assert jax.devices()[0].platform == "tpu", jax.devices()
H, KV, Dh, bs, C, window, ring, width = 128, 8, 128, 16, 512, 4096, 288, 1024
key = jax.random.split(jax.random.PRNGKey(0), 3)
rs = np.random.RandomState(0)
q = jax.random.normal(key[0], (1, C, H, Dh), jnp.bfloat16)
nblocks = 1 + width
ck = pool_rows(jax.random.normal(key[1], (nblocks * bs, KV, Dh), jnp.bfloat16))
cv = pool_rows(jax.random.normal(key[2], (nblocks * bs, KV, Dh), jnp.bfloat16))
full_tbl = jnp.asarray(rs.permutation(np.arange(1, nblocks))[None], jnp.int32)
ring_tbl = full_tbl[:, :ring]
kw = dict(kv_heads=KV, block_size=bs, scale=None)


def chain(n, fn):
    def run(q, tbl, pos):
        out = None
        for _ in range(n):
            out = fn(q, ck, cv, tbl, pos)
            q = q + (out.reshape(q.shape) * 1e-30).astype(q.dtype)
        return out
    return jax.jit(run)


WAYS = {
    "sliding_walk": (ring_tbl, lambda q, k, v, t, p: grouped_attention_pallas(
        q, k, v, t, p, window=window, newest=p[:, -1], **kw)),
    "ring_gather": (ring_tbl, lambda q, k, v, t, p:
                    grouped_attention_reference(
                        q, k, v, t, p, window=window, newest=p[:, -1], **kw)),
    "full_walk": (full_tbl, lambda q, k, v, t, p: grouped_attention_pallas(
        q, k, v, t, p, **kw)),
}


def timed(fn, *a, reps=5):
    fn(*a).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


for name, (tbl, fn) in WAYS.items():
    nine, one = chain(9, fn), chain(1, fn)
    for start in (0, 1536, 3584, 4096, 8192, 14336):
        pos = jnp.asarray(start + np.arange(C)[None], jnp.int32)
        ms = (timed(nine, q, tbl, pos) - timed(one, q, tbl, pos)) / 8 * 1e3
        print(json.dumps({"probe": name, "start": start,
                          "device_ms_a_call": round(ms, 4)}), flush=True)
