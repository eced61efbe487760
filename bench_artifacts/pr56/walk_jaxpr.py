"""sha256 of the walk's jaxpr (kernel body included, no source locations
in it) at the call sites PR 56 must leave as they were: GPT-2 xl's decode
and verify calls (G = 1), EvaByte's decode call (the window),
DeepSeek-V2-Lite's latent decode call (`chatgen`) and the
grouped tiles of Granite 4.0-H (`chatrate`) and Command A+'s full layer
(`mixedlen`).  `stablehlo_sha.py` cuts the kernel's serialized body out
of the programs it hashes; this compares the body.  Run in the parent's
checkout and in the change's:

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python walk_jaxpr.py
"""
import hashlib

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import pallas_backend

pallas_backend.interpret = lambda: False
from deepspeed_tpu.kernels import eva, paged  # noqa: E402
from deepspeed_tpu.serving.kv_cache import pool_width  # noqa: E402

S = jax.ShapeDtypeStruct
bf = jnp.bfloat16


def gpt(T):
    pool = S((513 * 16, pool_width(25, 64)), bf)
    return jax.make_jaxpr(lambda *a: paged.paged_attention_pallas(
        *a, kv_mode="dense", block_size=16))(
        S((16, T, 25, 64), bf), pool, pool, S((16, 64), jnp.int32),
        S((16, T), jnp.int32))


def evab():
    pool = S((1537 * 16, 4096), bf)
    return jax.make_jaxpr(lambda *a: eva.eva_attention_pallas(
        *a, window=2048, chunk=16, block_size=16))(
        S((8, 1, 32, 128), bf), pool, pool, S((8, 192), jnp.int32),
        S((8, 1), jnp.int32))


def grouped(slots, H, KV, Dh, nblocks, W, scale):
    pool = S((nblocks * 16, pool_width(KV, Dh)), bf)
    return jax.make_jaxpr(lambda *a: paged.grouped_attention_pallas(
        *a, kv_heads=KV, block_size=16, scale=scale))(
        S((slots, 1, H, Dh), bf), pool, pool, S((slots, W), jnp.int32),
        S((slots, 1), jnp.int32))


def latent():
    return jax.make_jaxpr(lambda *a: paged.latent_attention_pallas(
        *a, block_size=16, rank=512, scale=0.1147))(
        S((32, 1, 16, 576), bf), S((8193 * 16, 640), bf),
        S((32, 256), jnp.int32), S((32, 1), jnp.int32))


for name, j in (("gpt_decode", gpt(1)), ("gpt_verify4", gpt(4)),
                ("eva_decode", evab()), ("latent_decode", latent()),
                ("granite_decode", grouped(64, 32, 8, 64, 8193, 128,
                                           1 / 64)),
                ("command_a_full_decode", grouped(16, 128, 8, 128, 16385,
                                                  1024, None))):
    t = str(j)
    print(name, hashlib.sha256(t.encode()).hexdigest(), len(t))
