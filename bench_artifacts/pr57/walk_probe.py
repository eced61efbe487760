"""The grouped walks against their oracle on the chip at the longchat
cell's tile (16 query heads on 2 K/V heads of 256) and at neighbours of
it: a decode step's walk and a prefill chunk's (my chip runs, PR 57).

    python3 bench_artifacts/pr57/walk_probe.py
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


def run(name, slots, q_len, start, heads=16, kv=2, dh=256, bs=16,
        width=384, nblocks=3200, normed=True):
    from deepspeed_tpu.kernels import registry
    from deepspeed_tpu.serving.kv_cache import pool_width

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    dtype = jnp.bfloat16
    q = jax.random.normal(k[0], (slots, q_len, heads, dh))
    ck = jax.random.normal(k[1], (nblocks * bs, kv, dh))
    if normed:      # unit RMS rows, as the model's q/k norm leaves them
        q = q / jnp.sqrt(jnp.mean(q * q, -1, keepdims=True))
        ck = ck / jnp.sqrt(jnp.mean(ck * ck, -1, keepdims=True))
    q = q.astype(dtype)
    pad = pool_width(kv, dh) - kv * dh
    ck = jnp.pad(ck.reshape(nblocks * bs, kv * dh),
                 ((0, 0), (0, pad))).astype(dtype)
    cv = jnp.pad(jax.random.normal(k[2], (nblocks * bs, kv * dh)),
                 ((0, 0), (0, pad))).astype(dtype)
    tables = (jax.random.permutation(k[3], nblocks - 1)[:slots * width]
              .reshape(slots, width).astype(jnp.int32) + 1)
    if q_len == 1:
        pos = jnp.asarray(start, jnp.int32)[:, None]
    else:
        pos = (jnp.arange(q_len)[None] + start).astype(jnp.int32)
    info = {"block_size": bs, "table_width": width, "q_len": q_len,
            "num_heads": heads, "head_dim": dh, "kv_mode": "dense",
            "kv_itemsize": 2, "kv_heads": kv, "window": 0, "ring": False,
            "batch": slots}
    kw = dict(info=info, kv_heads=kv, block_size=bs, scale=None, window=0,
              newest=None)
    chosen = registry.resolve_impl("grouped_attention", info=info)
    f = lambda impl: jax.jit(lambda *a: registry.dispatch(
        "grouped_attention", *a, impl=impl, **kw))(q, ck, cv, tables, pos)
    want = np.asarray(f("jnp"), np.float32)
    got = np.asarray(f(None), np.float32)
    d = np.abs(got - want)
    truth = None
    if q_len == 1:      # a third opinion, on the host in float64
        G = heads // kv
        rows_k = np.asarray(ck, np.float64)[:, :kv * dh].reshape(
            nblocks, bs, kv, dh)
        rows_v = np.asarray(cv, np.float64)[:, :kv * dh].reshape(
            nblocks, bs, kv, dh)
        truth = np.zeros((slots, heads * dh))
        for b in range(slots):
            n = int(pos[b, 0]) + 1
            kk = rows_k[np.asarray(tables[b])].reshape(-1, kv, dh)[:n]
            vv = rows_v[np.asarray(tables[b])].reshape(-1, kv, dh)[:n]
            for h in range(heads):
                sc = kk[:, h // G] @ np.asarray(q[b, 0, h], np.float64) \
                    * dh ** -0.5
                pr = np.exp(sc - sc.max())
                truth[b, h * dh:(h + 1) * dh] = pr / pr.sum() @ vv[:, h // G]
        truth = {"oracle_vs_host": float(np.abs(
            want.reshape(slots, -1) - truth).max()),
            "auto_vs_host": float(np.abs(
                got.reshape(slots, -1) - truth).max())}
    by_query = d.reshape(slots, q_len, -1).max(-1)
    print(json.dumps({
        "case": name, "auto": chosen, "max_diff": float(d.max()),
        "mean_diff": float(d.mean()), "ref_std": float(want.std()),
        "host": truth,
        "worst_queries": np.argsort(-by_query.max(0))[:6].tolist(),
        "by_slot": by_query.max(1).round(4).tolist()[:8]}), flush=True)


def main():
    if len(sys.argv) > 1:  # a rehearsal off the chip, small
        return run("toy", 2, 1, [37, 100], width=16, nblocks=64)
    starts = [37, 2047, 4100, 6143, 5, 255, 1024, 3000]
    run("decode_H16_KV2_Dh256", 8, 1, starts)
    run("decode_H16_KV4_Dh128", 8, 1, starts, kv=4, dh=128)
    run("decode_H8_KV2_Dh256", 8, 1, starts, heads=8)
    run("decode_H32_KV2_Dh256", 8, 1, starts, heads=32)
    run("decode_H16_KV1_Dh512", 8, 1, starts, kv=1, dh=512)
    run("decode_H16_KV2_Dh128", 8, 1, starts, kv=2, dh=128)
    run("decode_H32_KV4_Dh256", 8, 1, starts, heads=32, kv=4)
    if "--prefill" in sys.argv:
        run("prefill_H16_KV2_Dh256_at0", 1, 512, 0)
        run("prefill_H16_KV2_Dh256_at3584", 1, 512, 3584)


if __name__ == "__main__":
    main()
