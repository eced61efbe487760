"""Tokens the engine serves on the CPU (the toy widths of tests/
test_deepseek_v2.py, float32 and bf16; Granite's and Command A+'s toys
too, whose oracle now shares `_table_rows`), as a digest: run in the
parent's checkout and in the change's — off the chip the registry answers
the oracle, which is the expression the layer ran before, so the two must
print the same lines.

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python cpu_tokens.py
"""
import hashlib

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import (Cohere2Moe, Cohere2MoeConfig, DeepSeekV2,
                                  DeepSeekV2Config, GraniteHybrid,
                                  GraniteHybridConfig)
from deepspeed_tpu.models.deepseek_v2 import Yarn
from deepspeed_tpu.serving import ServeConfig, ServeEngine


def prompts(vocab, lengths):
    return [np.random.RandomState(i).randint(0, vocab, (n,)).tolist()
            for i, n in enumerate(lengths)]


def served(name, model, serve, vocab, lengths, new):
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    out = ServeEngine(model, params, serve).generate(
        prompts(vocab, lengths), new)
    print(name, hashlib.sha256(repr(out).encode()).hexdigest()[:16],
          [o[-3:] for o in out], flush=True)


for dtype in (jnp.float32, jnp.bfloat16):
    served(f"deepseek-v2 {jnp.dtype(dtype).name}", DeepSeekV2(
        DeepSeekV2Config(
            vocab_size=128, max_seq_len=128, num_layers=3, num_heads=4,
            d_model=64, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, d_ff=96, first_k_dense=1,
            num_experts=8, top_k=3, num_shared_experts=1, d_expert=48,
            yarn=Yarn(40.0, 64, 32.0, 1.0, 0.707, 0.707), init_std=0.2,
            router_std=1.0, param_dtype=dtype)),
        ServeConfig(block_size=8, num_blocks=40, max_batch=3,
                    prefill_chunk=16, max_seq_len=128, prefix_cache=False),
        128, (8, 21, 33), 24)
    served(f"granite {jnp.dtype(dtype).name}", GraniteHybrid(
        GraniteHybridConfig(
            vocab_size=97, max_seq_len=64, num_layers=6, period=3,
            attention_at=(1,), d_model=32, d_ffn=64, num_heads=4,
            kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=8,
            ssm_state=16, ssm_conv=4, ssm_chunk=4, init_std=0.2,
            param_dtype=dtype)),
        ServeConfig(block_size=4, num_blocks=64, max_batch=3,
                    prefill_chunk=8, max_seq_len=64, prefix_cache=False),
        97, (8, 19, 30), 12)
    served(f"command-a {jnp.dtype(dtype).name}", Cohere2Moe(
        Cohere2MoeConfig(
            vocab_size=128, max_seq_len=256, num_layers=8, num_heads=8,
            kv_heads=2, head_dim=16, d_model=64, d_expert=32,
            num_experts=8, top_k=4, num_shared=2, window=32, init_std=0.2,
            param_dtype=dtype)),
        ServeConfig(block_size=8, num_blocks=120, max_batch=3,
                    prefill_chunk=16, max_seq_len=256, prefix_cache=False),
        128, (8, 65, 100), 12)
