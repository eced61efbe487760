"""The walk's jaxpr (kernel body included, no source locations) at the
G = 1 call sites' shapes: GPT-2 xl's decode and verify calls, EvaByte's
decode call."""
import sys, hashlib
import jax, jax.numpy as jnp
from deepspeed_tpu.ops import pallas_backend
pallas_backend.interpret = lambda: False
from deepspeed_tpu.kernels import paged, eva
from deepspeed_tpu.serving.kv_cache import pool_width
S = jax.ShapeDtypeStruct
bf = jnp.bfloat16
def gpt(T):
    pool = S((513 * 16, pool_width(25, 64)), bf)
    return jax.make_jaxpr(lambda *a: paged.paged_attention_pallas(*a, kv_mode="dense", block_size=16))(
        S((16, T, 25, 64), bf), pool, pool, S((16, 64), jnp.int32), S((16, T), jnp.int32))
def evab():
    pool = S((1537 * 16, 4096), bf)
    return jax.make_jaxpr(lambda *a: eva.eva_attention_pallas(*a, window=2048, chunk=16, block_size=16))(
        S((8, 1, 32, 128), bf), pool, pool, S((8, 192), jnp.int32), S((8, 1), jnp.int32))
for name, j in (("gpt_decode", gpt(1)), ("gpt_verify4", gpt(4)), ("eva_decode", evab())):
    t = str(j)
    print(name, hashlib.sha256(t.encode()).hexdigest(), len(t))
    open(sys.argv[1] + "_" + name + ".txt", "w").write(t)
