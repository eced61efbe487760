"""Where the decode walk over rows of TWO K/V heads goes wrong on the
chip (my chip runs, PR 57): `_block_diagonal` and `_own_lanes` against
numpy, the kernel's raw rows against the host's, and the same call made
as ONE K/V head of twice the width with the queries laid block-diagonal
by hand.

    python3 bench_artifacts/pr57/walk_probe2.py
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(slots=8, heads=16, kv=2, dh=256, bs=16, width=384, nblocks=3200):
    from deepspeed_tpu.kernels import paged
    from deepspeed_tpu.ops import pallas_backend

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(k[0], (slots, 1, heads, dh)).astype(jnp.bfloat16)
    ck = jax.random.normal(k[1], (nblocks * bs, kv * dh)).astype(jnp.bfloat16)
    cv = jax.random.normal(k[2], (nblocks * bs, kv * dh)).astype(jnp.bfloat16)
    tables = (jax.random.permutation(k[3], nblocks - 1)[:slots * width]
              .reshape(slots, width).astype(jnp.int32) + 1)
    starts = [37, 2047, 4100, 6143, 5, 255, 1024, 3000][:slots]
    pos = jnp.asarray(starts, jnp.int32)[:, None]
    G, W = heads // kv, kv * dh
    Hp = paged.score_rows(1, heads)

    # (1) the queries laid block-diagonal, on the device and on the host
    bd = np.asarray(jax.jit(lambda x: paged._block_diagonal(x, Hp, W, G))(q),
                    np.float32)
    want = np.zeros((slots, Hp, W), np.float32)
    qn = np.asarray(q, np.float32)
    for h in range(heads):
        n = h // G
        want[:, h, n * dh:(n + 1) * dh] = qn[:, 0, h]
    print(json.dumps({"block_diagonal_max_diff":
                      float(np.abs(bd - want).max())}), flush=True)

    # (2) the kernel's raw rows against the host's, every lane
    interpret = pallas_backend.interpret()
    raw = jax.jit(lambda *a: paged._walk(
        *a, kv_mode="dense", block_size=bs, interpret=interpret,
        kv_heads=kv, scale=None))(q, ck, cv, tables, pos)
    raw = np.asarray(raw, np.float32)          # after _own_lanes
    rows_k = np.asarray(ck, np.float64).reshape(nblocks, bs, W)
    rows_v = np.asarray(cv, np.float64).reshape(nblocks, bs, W)
    truth = np.zeros((slots, heads, dh))
    other = np.zeros((slots, heads, dh))   # the OTHER K/V head's answer
    for b in range(slots):
        n = starts[b] + 1
        kk = rows_k[np.asarray(tables[b])].reshape(-1, W)[:n]
        vv = rows_v[np.asarray(tables[b])].reshape(-1, W)[:n]
        for h in range(heads):
            for dest, head in ((truth, h // G), (other, 1 - h // G)):
                lanes = slice(head * dh, (head + 1) * dh)
                sc = kk[:, lanes] @ qn[b, 0, h].astype(np.float64) \
                    * dh ** -0.5
                pr = np.exp(sc - sc.max())
                dest[b, h] = pr / pr.sum() @ vv[:, lanes]
    got = raw.reshape(slots, heads, dh)
    by_head = np.abs(got - truth).max(-1)          # [slots, heads]
    print(json.dumps({
        "walk_kv2_vs_host_by_head_slot0": by_head[0].round(3).tolist(),
        "walk_kv2_vs_host_by_head_slot4": by_head[4].round(3).tolist(),
        "walk_kv2_vs_OTHER_head_slot4":
        np.abs(got - other).max(-1)[4].round(3).tolist()}), flush=True)

    # (3) the same attention as ONE K/V head of kv * dh lanes: the
    # queries block-diagonal by hand, the scale given, own lanes kept
    q1 = jnp.asarray(want[:, :heads], jnp.bfloat16)[:, None]   # [B,1,H,W]
    one = jax.jit(lambda *a: paged._walk(
        *a, kv_mode="dense", block_size=bs, interpret=interpret,
        kv_heads=1, scale=dh ** -0.5))(q1, ck, cv, tables, pos)
    one = np.asarray(one, np.float32).reshape(slots, heads, W)
    kept = np.stack([one[:, h, (h // G) * dh:(h // G + 1) * dh]
                     for h in range(heads)], axis=1)
    # (4) ways to keep each row's own lanes, on the device, from the raw
    # rows [B, T * Hp, W] (the call as one head hands them back whole)
    raw1 = jnp.asarray(one).reshape(slots, heads, W)

    def masked_sum(out):
        rows = out.reshape(slots, 1, heads, kv, dh)
        own = (jnp.arange(heads)[:, None] // G) == jnp.arange(kv)[None, :]
        return jnp.sum(jnp.where(own[None, None, :, :, None], rows, 0.0),
                       axis=3)

    def halves(out):
        lane = jnp.arange(W)[None, :] // dh
        own = lane == (jnp.arange(heads)[:, None] // G)
        kept = jnp.where(own[None], out, 0.0)
        return sum(kept[..., n * dh:(n + 1) * dh] for n in range(kv))

    def slices(out):
        return paged._own_lanes(out, 1, heads, G, dh)

    ways = {}
    for name, fn in (("masked_sum", masked_sum), ("halves", halves),
                     ("slices_as_today", slices)):
        got_w = np.asarray(jax.jit(fn)(raw1), np.float32).reshape(
            slots, heads, dh)
        ways[name] = float(np.abs(got_w - truth).max())
    print(json.dumps({"own_lanes_on_the_device_vs_host": ways}), flush=True)
    print(json.dumps({"walk_as_one_head_vs_host_max_diff":
                      float(np.abs(kept - truth).max()),
                      "walk_kv2_vs_host_max_diff":
                      float(np.abs(got - truth).max())}), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1:
        main(slots=5, heads=8, kv=2, dh=128, width=16, nblocks=100)
    else:
        main()
