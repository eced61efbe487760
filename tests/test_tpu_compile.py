"""Compile for the chip, without the chip.

The TPU compiler is installed here and compiles for a described
`v5e:2x2`.  Every registered Pallas kernel variant, at the widths the
main path runs (GPT-2 xl: d_model 1600, 25 heads of 64, vocab 50304,
seq 1024), either compiles natively — `tpu_custom_call` in the
optimized program — or is refused by the kernel registry BY NAME, with
the message the registry carries for it.  What `auto` selects on the
chip must compile; nothing else may reach the compiler.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold libtpu, and every xdist worker
imports this file.  Nothing runs on a device here, so nothing in this
file is a measurement.
"""

import dataclasses
from typing import Callable, Optional

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from deepspeed_tpu.kernels import registry


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep it off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, shapes, sharding):
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# -- the cases: Motivation 3's table ----------------------------------------

B, S, H, DH, D, V = 8, 1024, 25, 64, 1600, 50304


def _flash_fwd():
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    qkv = (_sds((B, S, H, DH), jnp.bfloat16),) * 3
    return (lambda q, k, v: flash_attention(q, k, v, causal=True)), qkv


def _flash_bwd(b=B, s=S, h=H, dh=DH, causal=True, bias=False, rate=0.0):
    """Forward + backward at the blocks `flash_blocks` picks: what the
    VMEM budget of that choice has to hold."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    shapes = (_sds((b, s, h, dh), jnp.bfloat16),) * 3
    if bias:  # the BERT padding-mask shape
        shapes += (_sds((b, 1, 1, s), jnp.float32),)

    def loss(q, k, v, *kb):
        kw = {"key_bias": kb[0]} if kb else {}
        if rate:
            kw.update(dropout_rate=rate, dropout_rng=jax.random.PRNGKey(0))
        return jnp.sum(flash_attention(q, k, v, causal=causal, **kw)
                       .astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2)), shapes


def _fused_xent():
    from deepspeed_tpu.ops.transformer.fused_xent import (
        fused_softmax_xent_sum, pick_blocks)

    # bf16, the training path's operands.  (At fp32 the chip refused
    # these blocks for 20.1 MB of its 16 MB scoped VMEM, which this
    # compile does NOT reproduce; chip_smoke runs fp32 at (256, 128).)
    n = B * S
    shapes = (_sds((n, D), jnp.bfloat16), _sds((D, V), jnp.bfloat16),
              _sds((n,), jnp.int32), _sds((n,), jnp.bool_))
    assert pick_blocks(n, V) == (256, 384)

    def loss(x, w, labels, valid):
        return fused_softmax_xent_sum(x, w, labels, valid,
                                      *pick_blocks(n, V))

    return jax.value_and_grad(loss, argnums=(0, 1)), shapes


def _sparse():
    from deepspeed_tpu.ops.sparse_attention.flash_sparse import \
        flash_sparse_attention

    nb = S // 128
    layout = np.tril(np.ones((H, nb, nb), np.int32))
    qkv = (_sds((2, S, H, DH), jnp.bfloat16),) * 3
    return (lambda q, k, v: flash_sparse_attention(
        q, k, v, layout, 128, causal=True)), qkv


def _paged(kv_mode, heads, dh, slots=16, bs=16, width=64, nblocks=1025,
           q_len=1, dtype=jnp.bfloat16):
    from deepspeed_tpu.serving.kv_cache import pool_width

    rows_total = nblocks * bs
    if kv_mode == "dense":
        cache = _sds((rows_total, pool_width(heads, dh)), dtype)
        qdt, item = dtype, jnp.dtype(dtype).itemsize
    else:
        w = dh if kv_mode == "int8" else dh // 2
        pdt = jnp.int8 if kv_mode == "int8" else jnp.uint8
        cache = (_sds((rows_total, pool_width(heads, w)), pdt),
                 _sds((rows_total, heads), jnp.float16))
        qdt, item = jnp.float32, 1
    shapes = (_sds((slots, q_len, heads, dh), qdt), cache, cache,
              _sds((slots, width), jnp.int32),
              _sds((slots, q_len), jnp.int32))
    info = {"block_size": bs, "table_width": width, "q_len": q_len,
            "num_heads": heads, "head_dim": dh, "kv_mode": kv_mode,
            "kv_itemsize": item}

    def fn(q, ck, cv, tables, q_pos):
        return registry.dispatch("paged_attention", q, ck, cv, tables,
                                 q_pos, info=info, kv_mode=kv_mode,
                                 block_size=bs)

    return fn, shapes, info


def _grouped(slots=64, heads=32, kv=8, dh=64, bs=16, width=128,
             nblocks=8193, q_len=1, scale=1 / 64, window=0, ring=False,
             dtype=jnp.bfloat16):
    """`grouped_attention` as a grouped layer's serving block calls it:
    Granite 4.0-H Micro's cell by default (64 slots, 32 query heads on 8
    K/V heads of 64, 8,193 blocks of 16 rows, a table of 128 entries,
    bf16 rows, scores times 1/64)."""
    from deepspeed_tpu.serving.kv_cache import pool_width

    cache = _sds((nblocks * bs, pool_width(kv, dh)), dtype)
    shapes = (_sds((slots, q_len, heads, dh), dtype), cache, cache,
              _sds((slots, width), jnp.int32),
              _sds((slots, q_len), jnp.int32))
    info = {"block_size": bs, "table_width": width, "q_len": q_len,
            "num_heads": heads, "head_dim": dh, "kv_mode": "dense",
            "kv_itemsize": jnp.dtype(dtype).itemsize, "kv_heads": kv,
            "window": window, "ring": ring, "batch": slots}
    if ring:
        shapes += (_sds((slots,), jnp.int32),)

    def fn(q, ck, cv, tables, q_pos, newest=None):
        return registry.dispatch("grouped_attention", q, ck, cv, tables,
                                 q_pos, info=info, kv_heads=kv,
                                 block_size=bs, scale=scale, window=window,
                                 newest=newest)

    return fn, shapes, info


def _command_a_full(**kw):
    """Command A+'s full layer in its cell: 16 slots, 128 query heads on
    8 K/V heads of 128, 16,385 blocks, a table of 1,024 entries."""
    return _grouped(**dict(dict(slots=16, heads=128, dh=128, width=1024,
                                nblocks=16385, scale=None), **kw))


def _latent(slots=32, heads=16, rank=512, rope=64, bs=16, width=256,
            nblocks=8193, q_len=1, kv_mode="dense", dtype=jnp.bfloat16):
    """`latent_attention` as a latent layer's serving block calls it in a
    decode step: DeepSeek-V2-Lite's cell by default (32 slots, 16 heads'
    absorbed queries over rows of 512 + 64 values in 640 lanes, 8,193
    blocks of 16 rows, a table of 256 entries, bf16 rows)."""
    from deepspeed_tpu.serving.kv_cache import pool_width

    w = rank + rope
    shapes = (_sds((slots, q_len, heads, w), dtype),
              _sds((nblocks * bs, pool_width(1, w)), dtype),
              _sds((slots, width), jnp.int32),
              _sds((slots, q_len), jnp.int32))
    info = {"block_size": bs, "table_width": width, "q_len": q_len,
            "num_heads": heads, "head_dim": w, "kv_mode": kv_mode,
            "kv_itemsize": jnp.dtype(dtype).itemsize, "kv_heads": 1}

    def fn(q_row, pool, tables, q_pos):
        return registry.dispatch("latent_attention", q_row, pool, tables,
                                 q_pos, info=info, block_size=bs, rank=rank,
                                 scale=0.1147)

    return fn, shapes, info


def _masked(batch=1, q_len=512, heads=64, nope=192, rope=64, v=256, rank=512,
            bs=16, width=1536, nblocks=12289, tile=1024,
            dtype=jnp.bfloat16):
    """A prefill chunk's attention over its selection as
    `sparse_latent_attend` calls it: the GLM cell's shapes by default
    (one request's 512 queries of 64 heads, latent rows of 512 + 64 in a
    pool of 640 lanes, a table of 1,536 blocks of 16, tiles of 1,024)."""
    import types

    from deepspeed_tpu.serving import sparse

    cfg = types.SimpleNamespace(kv_lora_rank=rank, v_head_dim=v,
                                qk_rope_head_dim=rope, head_dim=nope + rope,
                                yarn=None)
    s = types.SimpleNamespace(block_size=bs)
    shapes = (_sds((rank, heads * (nope + v)), dtype),
              _sds((batch, q_len, heads, nope), dtype),
              _sds((batch, q_len, heads, rope), dtype),
              _sds((nblocks * bs, 640), dtype),
              _sds((batch, width), jnp.int32),
              _sds((batch, q_len, width * bs), jnp.bool_),
              _sds((), jnp.int32))
    info = sparse.masked_info(cfg, shapes[1], shapes[2], shapes[3], shapes[0],
                              tile)

    def fn(kv_b, q_nope, q_rope, pool, tables, mask, n_tiles):
        return registry.dispatch(
            "masked_latent_attention", cfg, kv_b, q_nope, q_rope, pool,
            tables, mask, n_tiles, s, tile, info=info)
    return fn, shapes, info


def _eva(slots=8, heads=32, dh=128, bs=16, window=2048, chunk=16,
         summary_blocks=64, nblocks=1537, q_len=1, dtype=jnp.bfloat16):
    """`eva_attention` as EvaByte's serving block calls it: the cell's
    shapes by default (evabyte-d16.serve.longdoc: 8 slots, 32 heads of
    128, a table of 128 window + 64 summary blocks, bf16 rows)."""
    from deepspeed_tpu.serving.kv_cache import pool_width

    width = window // bs + summary_blocks
    cache = _sds((nblocks * bs, pool_width(heads, dh)), dtype)
    shapes = (_sds((slots, q_len, heads, dh), dtype), cache, cache,
              _sds((slots, width), jnp.int32),
              _sds((slots, q_len), jnp.int32))
    info = {"block_size": bs, "table_width": width, "q_len": q_len,
            "num_heads": heads, "head_dim": dh, "kv_mode": "dense",
            "kv_itemsize": jnp.dtype(dtype).itemsize,
            "window": window, "chunk": chunk}

    def fn(q, ck, cv, tables, q_pos):
        return registry.dispatch("eva_attention", q, ck, cv, tables, q_pos,
                                 info=info, window=window, chunk=chunk,
                                 block_size=bs)

    return fn, shapes, info


def _codec(variant, wire, n=4 * 1024 * 1024, block=256):
    info = {"block": block}
    if variant == "quantize":
        shapes = (_sds((n,), jnp.float32),)

        def fn(x):
            return registry.dispatch("quant_codec", x, block, wire,
                                     variant="quantize", info=info)
    else:
        w = block if wire == "int8" else block // 2
        pdt = jnp.int8 if wire == "int8" else jnp.uint8
        shapes = (_sds((n // block, w), pdt),
                  _sds((n // block,), jnp.float16))

        def fn(p, s):
            return registry.dispatch("quant_codec", p, s, wire, n,
                                     variant="dequantize", info=info)
    return fn, shapes, info


def _moe(variant, n=8192, d=768, e=8, k=2):
    cap = 2 * n * k // e
    info = {"model_dim": d}
    routing = (_sds((k, n), jnp.int32),)
    if variant == "dispatch":
        shapes = (_sds((n, d), jnp.float32),) + routing * 2 + (
            _sds((k, n), jnp.bool_),)

        def fn(x, eidx, pos, keep):
            return registry.dispatch("moe_dispatch", x, eidx, pos, keep,
                                     e, cap, variant="dispatch", info=info)
    else:
        shapes = (_sds((e, cap, d), jnp.float32),) + routing + (
            _sds((k, n), jnp.float32),) + routing + (
            _sds((k, n), jnp.bool_),)

        def fn(out, eidx, gate, pos, keep):
            return registry.dispatch("moe_dispatch", out, eidx, gate, pos,
                                     keep, variant="combine", info=info)
    return fn, shapes, info


def _touched(tokens, e, d, f, dtype=jnp.bfloat16):
    from deepspeed_tpu.moe.dropless import touched_info

    experts = {"gate": _sds((e, d, f), dtype), "up": _sds((e, d, f), dtype),
               "down": _sds((e, f, d), dtype)}
    info = touched_info(tokens, experts)
    shapes = (_sds((tokens, d), jnp.float32), experts,
              _sds((tokens, e), jnp.float32), _sds((e,), jnp.int32),
              _sds((), jnp.int32))

    def fn(x, experts, w, ids, n):
        return registry.dispatch("touched_experts", x, experts, w, ids, n,
                                 info=info)
    return fn, shapes, info


def _slab(tokens, rows, e, d, f, dtype=jnp.bfloat16):
    """The product over a slab of `rows` rows as `experts_slabs` calls
    it in a prefill chunk of `tokens` tokens."""
    from deepspeed_tpu.moe.dropless import grouped_info

    experts = {"gate": _sds((e, d, f), dtype), "up": _sds((e, d, f), dtype),
               "down": _sds((e, f, d), dtype)}
    info = grouped_info(tokens, rows, experts)
    shapes = (_sds((rows, d), dtype), experts, _sds((e + 1,), jnp.int32))

    def fn(xs, experts, offsets):
        return registry.dispatch("grouped_experts", xs, experts, offsets,
                                 info=info)
    return fn, shapes, info


def _ssm_step(slots=64, heads=64, p=64, n=128, dtype=jnp.float32):
    """`ssm_step` as `ssm_mix` calls it in a decode step: the Granite
    cell's shapes by default (64 slots, a float32 state of 64 heads x 64
    x 128 a slot)."""
    from deepspeed_tpu.kernels.ssm import ssm_step_info

    state = _sds((slots, heads, p, n), dtype)
    info = ssm_step_info(state)
    f32 = lambda *shape: _sds(shape, jnp.float32)
    shapes = (f32(slots, heads, p), f32(slots, n), f32(slots, n),
              f32(slots, heads), f32(heads), state,
              _sds((slots,), jnp.int32), _sds((), jnp.int32))

    def fn(*args):
        return registry.dispatch("ssm_step", *args, info=info)
    return fn, shapes, info


def _gdn_step(slots=48, heads=32, d=128, dtype=jnp.float32):
    """`gdn_step` as `gdn_mix` calls it in a decode step: the Qwen3-Next
    cell's shapes by default (48 slots, a float32 state of 32 heads x 128
    x 128 a slot)."""
    from deepspeed_tpu.kernels.gdn import gdn_step_info

    state = _sds((slots, heads, d, d), dtype)
    info = gdn_step_info(state)
    f32 = lambda *shape: _sds(shape, jnp.float32)
    shapes = (f32(slots, heads, d), f32(slots, heads, d),
              f32(slots, heads, d), f32(slots, heads), f32(slots, heads),
              state, _sds((slots,), jnp.int32), _sds((), jnp.int32))

    def fn(*args):
        return registry.dispatch("gdn_step", *args, info=info)
    return fn, shapes, info


@dataclasses.dataclass
class Case:
    """`build()` -> (fn, shapes[, info]).  `op` None: the kernel is
    called directly (the flash kernels, fused CE) and must compile.
    `refused`: None when `auto` selects the kernel on the chip and it
    must compile; else a regex the registry's refusal must match."""
    name: str
    build: Callable
    op: Optional[str] = None
    variant: str = "default"
    refused: Optional[str] = None


CASES = [
    Case("flash_fwd_B8_S1024_H25_Dh64_bf16", _flash_fwd),
    Case("flash_fwd_bwd_B8_S1024_H25_Dh64_bf16", _flash_bwd),
    # the benchmark cell's call, gpt2-xl-d24.train.seq1024
    Case("flash_fwd_bwd_B4_S1024_H25_Dh64_bf16_cell",
         lambda: _flash_bwd(b=4)),
    Case("flash_fwd_bwd_B1_S4096_H8_Dh128_bf16_long_wide",
         lambda: _flash_bwd(b=1, s=4096, h=8, dh=128)),
    # BERT-large at seq 512: bidirectional, key bias, in-kernel dropout
    Case("flash_fwd_bwd_B16_S512_H16_Dh64_bf16_full_bias_dropout",
         lambda: _flash_bwd(b=16, s=512, h=16, dh=64, causal=False,
                            bias=True, rate=0.1)),
    Case("fused_xent_fwd_bwd_N8192_D1600_V50304", _fused_xent),
    Case("flash_sparse_fwd_S1024_H25_Dh64_block128", _sparse),
    # one kernel for every head shape: the pool row is what it tiles
    Case("paged_dense_H16_Dh128", lambda: _paged("dense", 16, 128),
         op="paged_attention"),
    Case("paged_dense_H25_Dh64", lambda: _paged("dense", 25, 64),
         op="paged_attention"),
    # the chat cell's decode call (513 blocks) and a verify step of 4
    Case("paged_dense_H25_Dh64_cell",
         lambda: _paged("dense", 25, 64, nblocks=513),
         op="paged_attention"),
    Case("paged_dense_H25_Dh64_verify4",
         lambda: _paged("dense", 25, 64, q_len=4), op="paged_attention"),
    Case("paged_dense_H25_Dh64_fp32",
         lambda: _paged("dense", 25, 64, dtype=jnp.float32),
         op="paged_attention"),
    # a prefill chunk, and blocks that are no whole tiles of bf16 rows
    Case("paged_dense_H25_Dh64_prefill256",
         lambda: _paged("dense", 25, 64, slots=1, q_len=256),
         op="paged_attention", refused=r"q_len 256 is a prefill chunk"),
    Case("paged_dense_H25_Dh64_block8",
         lambda: _paged("dense", 25, 64, bs=8),
         op="paged_attention", refused=r"block of 8 rows is not whole"),
    # quantized rows: the scales tile of a block is one the chip's
    # compiler does not copy
    Case("paged_int8_H16_Dh128", lambda: _paged("int8", 16, 128),
         op="paged_attention", refused=r"int8 rows: .*\(16, 16\) tile"),
    Case("paged_int8_H25_Dh64", lambda: _paged("int8", 25, 64),
         op="paged_attention", refused=r"int8 rows: .*\(16, 25\) tile"),
    Case("paged_int4_H16_Dh128", lambda: _paged("int4", 16, 128),
         op="paged_attention", refused=r"int4 rows: .*\(16, 16\) tile"),
    Case("paged_int4_H25_Dh64", lambda: _paged("int4", 25, 64),
         op="paged_attention", refused=r"int4 rows: .*\(16, 25\) tile"),
    # EvaByte's decode call at the longdoc cell's shapes, and what the
    # shape rule sends to the oracle
    Case("eva_H32_Dh128_cell", _eva, op="eva_attention"),
    Case("eva_H32_Dh128_fp32", lambda: _eva(dtype=jnp.float32, bs=8,
                                            chunk=8, summary_blocks=256),
         op="eva_attention"),
    Case("eva_H32_Dh128_prefill1024",
         lambda: _eva(slots=1, q_len=1024),
         op="eva_attention", refused=r"q_len 1024 is a prefill chunk"),
    Case("eva_H32_Dh128_block8", lambda: _eva(bs=8, chunk=8),
         op="eva_attention", refused=r"block of 8 rows is not whole"),
    Case("eva_H32_Dh128_summaries_not_whole_blocks",
         lambda: _eva(chunk=256),
         op="eva_attention", refused=r"8 summary rows are not whole"),
    # grouped rows: a full layer's decode call at the two cells' tiles
    # (32 x 512 and 128 x 1,024), a verify step, and what keeps the
    # gather
    Case("grouped_H32_KV8_Dh64_chatrate", _grouped, op="grouped_attention"),
    Case("grouped_H128_KV8_Dh128_mixedlen_full", _command_a_full,
         op="grouped_attention"),
    Case("grouped_H32_KV8_Dh64_verify4", lambda: _grouped(q_len=4),
         op="grouped_attention"),
    # a prefill chunk: Command A+'s full layer takes the walk of the one
    # request's live blocks, Granite's heads of half a lane tile and a
    # chunk over two sequences keep the gather
    Case("grouped_H128_KV8_Dh128_mixedlen_prefill512",
         lambda: _command_a_full(slots=1, q_len=512),
         op="grouped_attention"),
    Case("grouped_H32_KV8_Dh64_prefill512",
         lambda: _grouped(slots=1, q_len=512),
         op="grouped_attention",
         refused=r"8 K/V heads of 64 values.*whole 128-lane tiles"),
    Case("grouped_H128_KV8_Dh128_prefill512_two_sequences",
         lambda: _command_a_full(slots=2, q_len=512),
         op="grouped_attention",
         refused=r"2 sequences of 512 queries.*one request's table"),
    # a sliding layer's decode call walks its window's live blocks modulo
    # the run: on the table, and in the cell's ring (16 slots, 288 blocks
    # of 16 rows); and so does the CHUNK, a tile of its queries a
    # program, at the cell's shapes (one request, 288 blocks of 16)
    Case("grouped_H128_KV8_Dh128_window_on_the_table",
         lambda: _command_a_full(window=4096),
         op="grouped_attention"),
    Case("grouped_H128_KV8_Dh128_mixedlen_ring",
         lambda: _command_a_full(window=4096, ring=True, width=288,
                                 nblocks=16 * 288 + 1),
         op="grouped_attention"),
    Case("grouped_H128_KV8_Dh128_prefill512_window_on_the_table",
         lambda: _command_a_full(slots=1, q_len=512, window=4096),
         op="grouped_attention"),
    Case("grouped_H128_KV8_Dh128_prefill512_mixedlen_ring",
         lambda: _command_a_full(slots=1, q_len=512, window=4096, ring=True,
                                 width=288, nblocks=16 * 288 + 1),
         op="grouped_attention"),
    # latent rows: a decode call at the chatgen cell's tile (16 score
    # rows of 640 lanes over one operand), a verify step, DeepSeek-V2's
    # 128 heads, and what keeps the gather
    Case("latent_H16_W576_chatgen", _latent, op="latent_attention"),
    Case("latent_H16_W576_verify4", lambda: _latent(q_len=4),
         op="latent_attention"),
    Case("latent_H128_W576", lambda: _latent(heads=128),
         op="latent_attention"),
    Case("latent_H16_W576_prefill512",
         lambda: _latent(slots=1, q_len=512),
         op="latent_attention", refused=r"q_len 512 is a prefill chunk"),
    Case("latent_H16_W576_block8", lambda: _latent(bs=8),
         op="latent_attention", refused=r"block of 8 rows is not whole"),
    # a prefill chunk over a learned selection (PR 60): the GLM cell's
    # call, and what the shape rule keeps from the compiler
    Case("masked_latent_T512_H64_tile1024_longctx", _masked,
         op="masked_latent_attention"),
    Case("masked_latent_T256_H16_tile512", lambda: _masked(
        q_len=256, heads=16, width=256, nblocks=1025, tile=512),
        op="masked_latent_attention"),
    Case("masked_latent_two_sequences", lambda: _masked(batch=2),
         op="masked_latent_attention", refused="2 sequences of 512"),
    Case("masked_latent_fp32_rows", lambda: _masked(dtype=jnp.float32),
         op="masked_latent_attention", refused="bytes a value"),
    Case("masked_latent_heads_of_96_values", lambda: _masked(nope=32, v=96),
         op="masked_latent_attention", refused="whole 128-lane"),
    Case("codec_quantize_int8_4M_block256",
         lambda: _codec("quantize", "int8"),
         op="quant_codec", variant="quantize"),
    Case("codec_quantize_int4_4M_block256",
         lambda: _codec("quantize", "int4"),
         op="quant_codec", variant="quantize"),
    Case("codec_dequantize_int8_4M_block256",
         lambda: _codec("dequantize", "int8"),
         op="quant_codec", variant="dequantize"),
    Case("codec_dequantize_int4_4M_block256",
         lambda: _codec("dequantize", "int4"),
         op="quant_codec", variant="dequantize"),
    # the routed product of a decode step at the two MoE cells' shapes
    # (chatgen: an expert's 1,408 columns as one tile; mixedlen: tiles
    # of 1,024), and what the shape rule sends elsewhere
    Case("touched_experts_T32_E64_D2048_F1408_chatgen",
         lambda: _touched(32, 64, 2048, 1408), op="touched_experts"),
    Case("touched_experts_T16_E16_D4096_F4096_mixedlen",
         lambda: _touched(16, 16, 4096, 4096), op="touched_experts"),
    Case("touched_experts_T4_E8_D1024_F512_fp32",
         lambda: _touched(4, 8, 1024, 512, jnp.float32),
         op="touched_experts"),
    Case("touched_experts_T512_prefill",
         lambda: _touched(512, 16, 1024, 512),
         op="touched_experts", refused=r"512 rows are over the ridge"),
    # the routed product of a prefill chunk's slab at the four MoE cells'
    # shapes (longchat and chatgen: an expert's columns as one tile;
    # mixedlen: tiles of 1,024; longctx: of 512), and what the shape
    # rule sends to XLA's grouped products
    Case("grouped_experts_C1280_E64_D2048_F512_longchat",
         lambda: _slab(512, 1280, 64, 2048, 512), op="grouped_experts"),
    Case("grouped_experts_C1024_E16_D4096_F4096_mixedlen",
         lambda: _slab(512, 1024, 16, 4096, 4096), op="grouped_experts"),
    Case("grouped_experts_C512_E16_D6144_F2048_longctx",
         lambda: _slab(512, 512, 16, 6144, 2048), op="grouped_experts"),
    Case("grouped_experts_C3072_E64_D2048_F1408_chatgen",
         lambda: _slab(512, 3072, 64, 2048, 1408), op="grouped_experts"),
    Case("grouped_experts_C256_E8_D1024_F512_fp32",
         lambda: _slab(256, 256, 8, 1024, 512, jnp.float32),
         op="grouped_experts"),
    Case("grouped_experts_T64_decode",
         lambda: _slab(64, 256, 16, 1024, 512), op="grouped_experts",
         refused=r"64 rows are under the ridge"),
    Case("grouped_experts_D1000_lanes",
         lambda: _slab(512, 1024, 16, 1000, 512), op="grouped_experts",
         refused=r"rows of 1000 values are not whole 128-lane"),
    Case("grouped_experts_C16384_D8192_vmem",
         lambda: _slab(2048, 16384, 16, 8192, 2048),
         op="grouped_experts", refused=r"do not fit the kernel's\s+VMEM"),
    # the recurrence of a decode step at the Granite cell's shapes (a
    # slot's 2 MB of state as one block), a state of 16 MB a slot in
    # tiles of 16 heads, and what the shape rule sends to the oracle
    Case("ssm_step_B64_H64_P64_N128_chatrate", _ssm_step, op="ssm_step"),
    Case("ssm_step_B8_H128_P128_N256_head_tiles",
         lambda: _ssm_step(8, 128, 128, 256), op="ssm_step"),
    Case("ssm_step_B64_H4_P8_N16_toy", lambda: _ssm_step(64, 4, 8, 16),
         op="ssm_step", refused=r"8 x 16 values of 4 bytes is not whole"),
    Case("ssm_step_B64_H64_P64_N128_bf16_state",
         lambda: _ssm_step(dtype=jnp.bfloat16),
         op="ssm_step", refused=r"64 x 128 values of 2 bytes is not whole"),
    # the delta rule of a decode step at the Qwen3-Next cell's shapes (a
    # slot's 2 MB of state as one block of 32 heads), a state in tiles of
    # 32 of 64 heads, and what the shape rule sends to the oracle
    Case("gdn_step_B48_H32_D128_longchat", _gdn_step, op="gdn_step"),
    Case("gdn_step_B8_H64_D128_head_tiles", lambda: _gdn_step(8, 64),
         op="gdn_step"),
    Case("gdn_step_B48_H4_D16_toy", lambda: _gdn_step(48, 4, 16),
         op="gdn_step", refused=r"16 x 16 values of 4 bytes is not square"),
    Case("gdn_step_B48_H32_D128_bf16_state",
         lambda: _gdn_step(dtype=jnp.bfloat16), op="gdn_step",
         refused=r"128 x 128 values of 2 bytes is not square"),
    Case("moe_dispatch_N8192_D768_E8", lambda: _moe("dispatch"),
         op="moe_dispatch", variant="dispatch", refused=r"one-row block"),
    Case("moe_combine_N8192_D768_E8", lambda: _moe("combine"),
         op="moe_dispatch", variant="combine", refused=r"one-row block"),
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_kernel_compiles_or_is_refused_by_name(case, one_chip, native):
    fn, shapes, *rest = case.build()
    info = rest[0] if rest else None
    if case.op is not None:
        chosen = registry.resolve_impl(case.op, case.variant, info=info)
        assert chosen == ("jnp" if case.refused else "pallas")
    if case.refused:
        # forced, the registry says why — the compiler is never asked
        with pytest.raises(RuntimeError, match=case.refused):
            registry.resolve_impl(case.op, case.variant, impl="pallas",
                                  info=info)
        text = _compile_text(fn, shapes, one_chip)  # auto: the oracle
        if case.op == "grouped_experts":
            # the oracle's `lax.ragged_dot` is a custom call of XLA's own;
            # the op's name stands in the text as the registry's scope
            # alone (PR 59: `oracle.grouped_experts`), never as a kernel
            assert "kernel.grouped_experts" not in text
            assert "grouped_experts" not in text.replace(
                "oracle.grouped_experts", "") and "ragged-dot" in text
        else:
            assert "tpu_custom_call" not in text
        return
    assert "tpu_custom_call" in _compile_text(fn, shapes, one_chip)


# -- what `auto` picks in each benchmark cell --------------------------------


def _train_attention(b, s, h, dh, bias=False):
    from deepspeed_tpu.ops.transformer.attention import flash_info

    q = _sds((b, s, h, dh), jnp.bfloat16)
    return "flash_attention", flash_info(
        q, q, _sds((b, 1, 1, s), jnp.float32) if bias else None)


def _serve_attention(q_len, slots):
    return "paged_attention", _paged("dense", H, DH, slots=slots,
                                     nblocks=513, q_len=q_len)[2]


@pytest.mark.parametrize("cell,call,expect", [
    # micro 4 x 1024, 25 heads of 64, causal, no bias
    ("gpt2-xl-d24.train.seq1024", lambda: _train_attention(4, 1024, H, DH),
     "pallas"),
    # micro 64 x 128, 16 heads of 64, padding mask: below _FLASH_MIN_SEQ
    ("bert-large.train.seq128",
     lambda: _train_attention(64, 128, 16, 64, bias=True), "jnp"),
    # no cell yet (ROADMAP C2): BERT-large's second phase
    ("bert-large.seq512", lambda: _train_attention(16, 512, 16, 64,
                                                   bias=True), "pallas"),
    # 16 slots, 513 blocks of 16, a table 64 wide, bf16
    ("gpt2-xl.serve.chat.decode", lambda: _serve_attention(1, 16),
     "pallas"),
    ("gpt2-xl.serve.chat.prefill", lambda: _serve_attention(256, 1), "jnp"),
    ("gpt2-xl.serve.overload.decode", lambda: _serve_attention(1, 16),
     "pallas"),
    # 8 slots, 1,537 blocks of 16, a table of 128 + 64 entries, bf16
    ("evabyte-d16.serve.longdoc.decode",
     lambda: ("eva_attention", _eva()[2]), "pallas"),
    ("evabyte-d16.serve.longdoc.prefill",
     lambda: ("eva_attention", _eva(slots=1, q_len=1024)[2]), "jnp"),
    # 64 slots, 8,193 blocks of 16, a table of 128 entries, bf16
    ("granite-4.0-h-micro.serve.chatrate.decode",
     lambda: ("grouped_attention", _grouped()[2]), "pallas"),
    ("granite-4.0-h-micro.serve.chatrate.prefill",
     lambda: ("grouped_attention", _grouped(slots=1, q_len=512)[2]), "jnp"),
    # 16 slots; the full layer's table of 1,024, the sliding ones' rings
    ("command-a-plus-d4.serve.mixedlen.decode.full",
     lambda: ("grouped_attention", _command_a_full()[2]), "pallas"),
    ("command-a-plus-d4.serve.mixedlen.decode.sliding",
     lambda: ("grouped_attention",
              _command_a_full(window=4096, ring=True, width=288)[2]),
     "pallas"),
    # one request's chunk of 512: the full layer walks, and the rings
    # (window + chunk = 4,608 rows, one more than the mask needs)
    ("command-a-plus-d4.serve.mixedlen.prefill.full",
     lambda: ("grouped_attention",
              _command_a_full(slots=1, q_len=512)[2]), "pallas"),
    ("command-a-plus-d4.serve.mixedlen.prefill.sliding",
     lambda: ("grouped_attention",
              _command_a_full(slots=1, q_len=512, window=4096, ring=True,
                              width=288)[2]), "pallas"),
    # 48 slots, 39,937 blocks of 16, a table of 832 entries, 16 query
    # heads on 2 K/V heads of 256, bf16: both calls walk
    ("qwen3-next-80b-a3b-d12.serve.longchat.decode",
     lambda: ("grouped_attention", _grouped(
         slots=48, heads=16, kv=2, dh=256, width=832, nblocks=39937,
         scale=None)[2]), "pallas"),
    ("qwen3-next-80b-a3b-d12.serve.longchat.prefill",
     lambda: ("grouped_attention", _grouped(
         slots=1, heads=16, kv=2, dh=256, width=832, nblocks=39937,
         q_len=512, scale=None)[2]), "pallas"),
    # 32 slots, 8,193 blocks of 16 latent rows of 576 values, a table of
    # 256 entries, bf16
    ("deepseek-v2-lite-d9.serve.chatgen.decode",
     lambda: ("latent_attention", _latent()[2]), "pallas"),
    ("deepseek-v2-lite-d9.serve.chatgen.prefill",
     lambda: ("latent_attention", _latent(slots=1, q_len=512)[2]), "jnp"),
    # PR 58: a prefill chunk's routed product, a slab of the rows held —
    # 3,072 of 3,072, 1,024 of 4,096, 512 of 4,096, 1,280 of 5,120
    ("deepseek-v2-lite-d9.serve.chatgen.prefill.routed",
     lambda: ("grouped_experts", _slab(512, 3072, 64, 2048, 1408)[2]),
     "pallas"),
    ("command-a-plus-d4.serve.mixedlen.prefill.routed",
     lambda: ("grouped_experts", _slab(512, 1024, 16, 4096, 4096)[2]),
     "pallas"),
    ("glm-5.2-d5.serve.longctx.prefill.routed",
     lambda: ("grouped_experts", _slab(512, 512, 16, 6144, 2048)[2]),
     "pallas"),
    ("qwen3-next-80b-a3b-d12.serve.longchat.prefill.routed",
     lambda: ("grouped_experts", _slab(512, 1280, 64, 2048, 512)[2]),
     "pallas"),
    # PR 60: a chunk's attention over its selection, one request
    ("glm-5.2-d5.serve.longctx.prefill.selection",
     lambda: ("masked_latent_attention", _masked()[2]), "pallas"),
    ("deepseek-v2-lite-d9.serve.chatgen.decode.routed",
     lambda: ("grouped_experts", _slab(32, 192, 64, 2048, 1408)[2]), "jnp"),
], ids=lambda v: v if isinstance(v, str) and "." in v else "")
def test_auto_choice_for_each_benchmark_cell(cell, call, expect, native):
    """The trace-time half of "the same numbers": at each cell's shapes
    the registry picks, on the chip and with nothing forced, what the
    cell was measured with."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_blocks

    op, info = call()
    assert registry.resolve_impl(op, info=info) == expect
    if op == "flash_attention" and expect == "pallas":
        assert flash_blocks(info["seq_len"], info["kv_len"]) == (512, 512)


# -- the partial-manual regions of ISSUE 22 (Motivation 1 and 2) -------------


def _mesh4(topo, shape, names):
    return Mesh(np.array(topo.devices).reshape(shape), names)


@pytest.mark.parametrize("shape,manual", [
    ((4, 1), {"data"}),            # the form the engine used to build
    ((4, 1), {"data", "model"}),   # MeshInfo.manual_axes' form
    ((2, 2), {"data"}),            # a real automatic axis stays automatic
], ids=["partial_size1", "full", "partial_size2"])
def test_bf16_psum_region_compiles_for_four_chips(topo, shape, manual):
    mesh = _mesh4(topo, shape, ("data", "model"))

    def wire(x):
        return jax.lax.psum(x.astype(jnp.bfloat16),
                            "data").astype(jnp.float32)

    f = jax.jit(jax.shard_map(wire, mesh=mesh, in_specs=P("data"),
                              out_specs=P(), axis_names=manual,
                              check_vma=False))
    x = jax.ShapeDtypeStruct((8, 1600), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    assert "all-reduce" in f.lower(x).compile().as_text()


def test_debug_callback_in_manual_region_compiles_for_four_chips(topo):
    """The MoE wire's byte counters are `jax.debug.callback`s inside
    the dispatch region (moe/dispatch.py); with the region made fully
    manual over a (data=4, size-1 rest) mesh they lower for the chip."""
    mesh = _mesh4(topo, (1, 4, 1, 1), ("pipe", "data", "seq", "model"))

    def body(x):
        jax.debug.callback(lambda n: None, jnp.sum(x > 0))
        return jax.lax.all_to_all(x, "data", 0, 0, tiled=True)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"),
                              axis_names=set(mesh.axis_names),
                              check_vma=False))
    x = jax.ShapeDtypeStruct((16, 768), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    assert "all-to-all" in f.lower(x).compile().as_text()


def test_flash_in_a_data_parallel_step_compiles_for_four_chips(topo, native):
    """What stopped ZeRO-2 over four chips in PR 22: under `jit` over a
    mesh XLA refuses to partition the Mosaic kernel.  The dispatcher
    now calls it per shard; forward and backward compile with the batch
    split over data=4."""
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.ops.transformer.attention import multihead_attention

    info = make_mesh(data=4, devices=topo.devices)
    qkv = (jax.ShapeDtypeStruct((4, S, H, DH), jnp.bfloat16,
                                sharding=info.sharding("data")),) * 3

    def loss(q, k, v):
        return jnp.sum(multihead_attention(q, k, v, causal=True)  # auto
                       .astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *qkv).compile().as_text()
    assert "tpu_custom_call" in text


def test_registry_refuses_a_kernel_xla_would_have_to_partition(topo, native):
    """Registry ops have no shard_map of their own: traced over a mesh
    of several devices outside one, `auto` takes the oracle and a forced
    kernel raises, where the compiler would."""
    from deepspeed_tpu.comm.mesh import make_mesh

    _, _, info = _paged("dense", 16, 128)
    assert registry.resolve_impl("paged_attention", info=info) == "pallas"
    make_mesh(data=2, model=2, devices=topo.devices)
    assert registry.resolve_impl("paged_attention", info=info) == "jnp"
    with pytest.raises(RuntimeError, match="cannot be automatically"):
        registry.resolve_impl("paged_attention", impl="pallas", info=info)


def _hlo_by_shape(text):
    """{dims: {(opcode, layout)}} of an optimised program's text."""
    import re

    by_shape = {}
    for m in re.finditer(r"= (\w+)\[([\d,]*)\]\{([\d,]*)[^ ]* ([\w\-]+)\(",
                         text):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        by_shape.setdefault(dims, set()).add((m.group(4), m.group(3)))
    return by_shape


def _kernels_by_scope(text):
    """{registry op: its Mosaic calls} of an optimised program's text,
    from the scope each `tpu_custom_call` was written under (PR 59:
    `kernels/registry.py::dispatch` wraps what it calls in
    `kernel.<op>`, and `monitor/tracing.py::program_scopes` reads the
    compiled instruction's path back): every one has such a path, under
    one of the serving programs' stages, and none lies under an
    `oracle.`."""
    import collections
    import re

    from deepspeed_tpu.monitor import tracing

    scopes = tracing.program_scopes(text)
    ops = collections.Counter()
    for ln in text.splitlines():
        if "tpu_custom_call" not in ln:
            continue
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln).group(1)
        parts = scopes[name].split("/")
        assert parts[0] in ("attn", "state", "ffn"), (name, scopes[name])
        assert not any(c.startswith("oracle.") for c in parts), scopes[name]
        op, = [c[len("kernel."):] for c in parts if c.startswith("kernel.")]
        assert op in registry.KERNEL_OPS, scopes[name]
        ops[op] += 1
    return dict(ops)


def test_decode_layer_uses_the_pool_as_it_lies(one_chip, native):
    """One layer of GPT-2 xl's `decode` at the chat cell's shapes (16
    slots, 513 blocks of 16, a table 64 wide, bf16): the pool enters
    row-major, is written by a scatter, is read by the kernel, and no
    operation of the pool's size changes its layout, converts it or
    gathers the table's width from it — what cost 133 of the parent's
    140 ms a step with the pool as `[rows, 25, 64]`."""
    from deepspeed_tpu.models import GPT, gpt2_config
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule
    from deepspeed_tpu.serving.kv_cache import pool_width

    slots, bs, nblocks, width = 16, 16, 513, 64
    model = GPT(gpt2_config("xl", num_layers=1, param_dtype=jnp.bfloat16))
    sched = ServeSchedule(max_batch=slots, prefill_chunk=256, block_size=bs,
                          num_blocks=nblocks, table_width=width)
    decode = ServeProgramBuilder(model, sched).build()["decode"]

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    rows = nblocks * bs
    pool = on((rows, pool_width(H, DH)), jnp.bfloat16)
    text = decode.lower(
        params, [(pool, pool)], on((slots,), jnp.int32),
        on((slots,), jnp.int32), on((slots,), jnp.bool_),
        on((slots, width), jnp.int32), on((slots,), jnp.float32),
        on((slots,), jnp.int32), on((slots,), jnp.uint32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert _kernels_by_scope(text) == {"paged_attention": 1}
    by_shape = _hlo_by_shape(text)
    pool_ops = by_shape[(rows, pool_width(H, DH))]
    assert {"parameter", "scatter"} <= {op for op, _ in pool_ops}
    assert {layout for _, layout in pool_ops} == {"1,0"}  # row-major
    moved = {"copy", "transpose", "convert", "gather", "reshape"}
    assert not moved & {op for op, _ in pool_ops}, pool_ops
    # nothing of the table's whole width (slots x 1,024 rows) is built
    wide = [d for d in by_shape if d and d[0] in (slots * width * bs,)
            or d[:2] == (slots, width * bs)]
    assert not wide, wide


def test_evabyte_decode_walks_the_pool_as_it_lies(one_chip, native):
    """EvaByte's 16-layer `decode` at the longdoc cell's shapes (8
    slots, 1,537 blocks of 16, a table of 128 + 64 entries, bf16): one
    kernel, called in every layer; every pool enters as `[rows, 4096]`
    row-major, is written by row scatters and is read by the kernel
    where it lies — no operation of a pool's size moves, converts or
    gathers it, and nothing of the table's whole width (8 x 3,072 rows,
    the parent's gather and its float32 copy: 61 of 71 ms a step) is
    built."""
    from deepspeed_tpu.models import EvaByte, EvaByteConfig
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule

    slots, bs, nblocks, layers = 8, 16, 1537, 16
    model = EvaByte(EvaByteConfig(max_seq_len=16384, num_layers=layers,
                                  param_dtype=jnp.bfloat16))
    width = 2048 // bs + 64
    sched = ServeSchedule(max_batch=slots, prefill_chunk=1024, block_size=bs,
                          num_blocks=nblocks, table_width=width,
                          window_blocks=2048 // bs)
    decode = ServeProgramBuilder(model, sched).build()["decode"]

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    rows = nblocks * bs
    pool = on((rows, 4096), jnp.bfloat16)
    compiled = decode.lower(
        params, [(pool, pool)] * layers, on((slots,), jnp.int32),
        on((slots,), jnp.int32), on((slots,), jnp.bool_),
        on((slots, width), jnp.int32), on((slots,), jnp.float32),
        on((slots,), jnp.int32), on((slots,), jnp.uint32),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == layers
    assert _kernels_by_scope(text) == {"eva_attention": layers}
    by_shape = _hlo_by_shape(text)
    pool_ops = by_shape[(rows, 4096)]
    assert {"parameter", "scatter"} <= {op for op, _ in pool_ops}
    assert {layout for _, layout in pool_ops} == {"1,0"}  # row-major
    moved = {"copy", "transpose", "convert", "gather", "reshape"}
    assert not moved & {op for op, _ in pool_ops}, pool_ops
    wide = [d for d in by_shape if d and (
        d[0] == slots * width or d[:2] in ((slots, width),
                                           (slots, width * bs)))
        and len(d) > 2]
    assert not wide, wide
    # the gathered tables were 1.2 GB of temporaries; a step now needs
    # what its weights' products do
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20



@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_deepseek_cell_programs_compile_inside_one_chip(program, one_chip,
                                                        native):
    """`deepseek-v2-lite-d9.serve.chatgen.decode` / `.prefill` at the
    cell's shapes (9 layers at published widths in bf16, 32 slots, 8,193
    blocks of 16 latent rows, a table of 256 entries, chunk 512), as the
    chip traces them: the registry answers the walk for a decode step's
    latent attention and the gather for a prefill chunk's, so `decode`'s
    custom calls are the 9 layers' walks of each slot's live blocks — no
    slot's whole table is gathered as `[8192, 16, 640]` — beside the 8
    routed layers' `touched_experts` kernels (32 rows are under the
    ridge), and the only ones in `prefill` are XLA's own grouped
    products (`lax.ragged_dot` over the 64 experts: 512 rows are over
    it); each pool enters as `[rows, 640]`, one array a layer, and in
    `decode` nothing of its size moves, converts or gathers it;
    and weights, pool and temporaries fit the chip's 15.75 GB with the
    room the check's 1.68 GB of reference logits needs."""
    from deepspeed_tpu.models import DeepSeekV2, DeepSeekV2Config
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule

    slots, bs, nblocks, layers, chunk = 32, 16, 8193, 9, 512
    model = DeepSeekV2(DeepSeekV2Config(num_layers=layers,
                                        param_dtype=jnp.bfloat16))
    width = 4096 // bs
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks,
                          table_width=width)
    progs = ServeProgramBuilder(model, sched).build()

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert abs(held - 10.36e9) < 0.01e9
    caches = [(on((nblocks * bs, 640), jnp.bfloat16),)] * layers
    if program == "decode":
        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, width), jnp.int32),
                on((slots,), jnp.float32), on((slots,), jnp.int32),
                on((slots,), jnp.uint32))
    else:
        args = (on((1, chunk), jnp.int32), on((), jnp.int32),
                on((), jnp.int32), on((width,), jnp.int32),
                on((), jnp.float32), on((), jnp.int32), on((), jnp.uint32))
    compiled = progs[program].lower(params, caches, *args).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    by_shape = _hlo_by_shape(text)
    pools = by_shape[(nblocks * bs, 640)]
    if program == "decode":
        assert sum("touched_experts" in ln for ln in calls) == layers - 1
        assert sum("paged_attention_walk" in ln for ln in calls) == layers
        assert len(calls) == 2 * layers - 1
        assert _kernels_by_scope(text) == {
            "touched_experts": layers - 1, "latent_attention": layers}
        assert (slots * width, bs, 640) not in by_shape
        moved = {"copy", "transpose", "convert", "gather", "reshape"}
        assert not moved & {op for op, _ in pools}, pools
    else:
        # PR 58: every routed layer's product walks one slab of the
        # chunk's 3,072 rows; XLA's own grouped products are gone
        assert sum("grouped_experts" in ln for ln in calls) == layers - 1
        assert len(calls) == layers - 1 and "ragged" not in text
        assert _kernels_by_scope(text) == {"grouped_experts": layers - 1}
    assert {layout for _, layout in pools} == {"1,0"}  # row-major
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + \
        m.output_size_in_bytes - m.alias_size_in_bytes
    print(program, "temp", m.temp_size_in_bytes / 1e9, "total", total / 1e9)
    assert m.temp_size_in_bytes < 512 << 20
    assert total < 15.75e9 - 1.68e9 - 1.0e9, total


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_glm_dsa_cell_programs_compile_inside_one_chip(program, one_chip,
                                                       native):
    """`glm-5.2-d5.serve.longctx.decode` / `.prefill` at the cell's
    shapes (5 layers at published widths in bf16, 16 of 256 experts,
    8 slots, 12,289 blocks of 16 rows, a table of 1,536 entries, chunk
    512), as the chip traces them: the selection is `jax.numpy` (a
    stable sort over the table's width in decode, a threshold over
    1,024-position tiles in prefill) and so is a decode step's attention
    over the 2,048 rows it lists, so `decode`'s only custom calls are
    the 4 routed layers' `touched_experts` kernels; a prefill chunk's
    attention over its mask is the `masked_latent_attention` kernel in
    each of the 5 layers (PR 60: a head's scores of a tile stay in VMEM,
    and no `[64, 512, 1024]` float32 is left in the program), beside the
    4 routed layers' `grouped_experts` kernels; a "full"
    layer's entry is (latent rows [rows, 640], index keys [rows, 128]),
    a "shared" layer's the latent rows alone; and weights, pools and
    temporaries leave the room the check's 1.9 GB of reference logits
    and its float32 pieces need."""
    from benchmarks import harness
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule

    config = harness.load_json("configs", "glm-5.2-d5.json")
    slots, bs, nblocks, chunk, seq = 8, 16, 12289, 512, 24576
    model = harness.plugin("models", "glm_moe_dsa").build(
        config, seq_len=seq, n_dev=1, param_dtype="bfloat16")
    layers, width = model.config.num_layers, seq // bs
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks,
                          table_width=width)
    progs = ServeProgramBuilder(model, sched).build()

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert abs(held - 7.763e9) < 0.01e9
    rows, keys = on((nblocks * bs, 640), jnp.bfloat16), \
        on((nblocks * bs, 128), jnp.bfloat16)
    caches = [(rows, keys) if kind == "full" else (rows,)
              for kind in model.config.indexer_types]
    assert [len(c) for c in caches] == [2, 1, 1, 1, 2]
    if program == "decode":
        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, width), jnp.int32),
                on((slots,), jnp.float32), on((slots,), jnp.int32),
                on((slots,), jnp.uint32))
    else:
        args = (on((1, chunk), jnp.int32), on((), jnp.int32),
                on((), jnp.int32), on((width,), jnp.int32),
                on((), jnp.float32), on((), jnp.int32), on((), jnp.uint32))
    compiled = progs[program].lower(params, caches, *args).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    if program == "decode":
        assert sum("touched_experts" in ln for ln in calls) == layers - 1
        assert _kernels_by_scope(text) == {"touched_experts": layers - 1}
    else:
        # PR 58: the 4 routed layers' products walk slabs of 512 of the
        # chunk's 4,096 rows; XLA's own grouped products are gone
        assert sum("grouped_experts" in ln for ln in calls) == layers - 1
        assert len(calls) == 2 * layers - 1 and "ragged" not in text
        # PR 60: the walk of the mask's tiles is one kernel a layer, and
        # a tile's scores for all heads are no buffer of the program
        assert _kernels_by_scope(text) == {
            "grouped_experts": layers - 1,
            "masked_latent_attention": layers}
        assert "f32[1,64,512,1024]" not in text
        assert "f32[64,512,1024]" not in text
    pools = _hlo_by_shape(text)[(nblocks * bs, 640)]
    assert {layout for _, layout in pools} == {"1,0"}  # row-major
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + \
        m.output_size_in_bytes - m.alias_size_in_bytes
    print(program, "temp", m.temp_size_in_bytes / 1e9, "total", total / 1e9)
    assert m.temp_size_in_bytes < 1536 << 20
    assert total < 15.75e9 - 1.9e9 - 2.5e9, total


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_command_a_plus_cell_programs_compile_inside_one_chip(program,
                                                              one_chip,
                                                              native):
    """`command-a-plus-d4.serve.mixedlen.decode` / `.prefill` at the
    cell's shapes (4 layers at published widths in bf16 with 16 of 128
    experts and an eighth of the vocabulary, 16 slots, 16,385 blocks of
    16 rows for the full layer and 16 x 288 + 1 for each sliding one, a
    table of 1,024 + 288 entries, chunk 512), as the chip traces them:
    the registry answers the walk for every layer's decode call (PR 62:
    the three rings' too, the window's live blocks modulo the ring) and
    for every layer's prefill call (PR 64: the three rings' too, a tile
    of the chunk's queries a program), so `decode`'s custom calls are
    the 4 layers' `touched_experts` kernels and the 4 layers' walks — no
    slot's whole table of 16,384 rows is laid out as `[16, 16384, 8,
    128]` and no slot's whole ring as `[16, 4608, 8, 128]` — and
    `prefill`'s are the 4 layers' `grouped_experts` kernels and the 4
    layers' prefill walks — the request's whole table is not gathered
    (`[1, 16384, 8, 128]`) nor scored (`[1, 1, 16, 512, 16384]`, a K/V
    head's queries against 16,384 rows), and neither is its whole ring
    (`[1, 4608, 8, 128]`, `[1, 1, 16, 512, 4608]`); every pool enters as
    `[rows, 1024]`, a K and a V a layer; and weights, pools and
    temporaries fit the chip's 15.75 GB with the room the check's 2.15 GB
    of reference logits needs."""
    from deepspeed_tpu.models import Cohere2Moe, Cohere2MoeConfig
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule
    from deepspeed_tpu.serving.layers import grouped_info

    slots, bs, nblocks, layers, chunk = 16, 16, 16385, 4, 512
    model = Cohere2Moe(Cohere2MoeConfig(
        vocab_size=32768, max_seq_len=16384, num_layers=layers,
        experts_held=16, param_dtype=jnp.bfloat16))
    width, ring = 16384 // bs, (4096 + chunk) // bs
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks,
                          table_width=width, ring_blocks=ring)
    spec = model.layer_spec()
    ask = lambda q_len, *kind: registry.resolve_impl(
        "grouped_attention", info=grouped_info(
            spec, model.config, sched, q_len, jnp.bfloat16, *kind))
    assert (ask(1), ask(1, 4096, True)) == ("pallas", "pallas")
    assert (ask(chunk), ask(chunk, 4096, True)) == ("pallas", "pallas")
    progs = ServeProgramBuilder(model, sched).build()

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert abs(held - 9.47e9) < 0.01e9
    full = on((nblocks * bs, 1024), jnp.bfloat16)
    window = on(((slots * ring + 1) * bs, 1024), jnp.bfloat16)
    caches = [(window, window)] * 3 + [(full, full)]
    pools = sum(2 * c[0].shape[0] * 1024 * 2 for c in caches)
    assert abs(pools - 1.98e9) < 0.01e9
    if program == "decode":
        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, width + ring), jnp.int32),
                on((slots,), jnp.float32), on((slots,), jnp.int32),
                on((slots,), jnp.uint32))
    else:
        args = (on((1, chunk), jnp.int32), on((), jnp.int32),
                on((), jnp.int32), on((width + ring,), jnp.int32),
                on((), jnp.float32), on((), jnp.int32), on((), jnp.uint32))
    compiled = progs[program].lower(params, caches, *args).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    if program == "decode":
        assert len(calls) == 2 * layers
        assert sum("touched_experts" in ln for ln in calls) == layers
        assert sum("paged_attention_walk" in ln for ln in calls) == layers
        assert not {(slots, 16384, 8, 128), (slots, 4608, 8, 128),
                    (slots * 4608 // 8, 8, 8, 128)} & set(
                        _hlo_by_shape(text))
        assert _kernels_by_scope(text) == {"touched_experts": layers,
                                           "grouped_attention": layers}
    else:
        walks = [ln for ln in calls if "paged_attention_prefill_walk" in ln]
        assert len(walks) == layers
        # PR 58: the 4 layers' routed products walk slabs of 1,024 of
        # the chunk's 4,096 rows; XLA's own grouped products are gone
        assert sum("grouped_experts" in ln for ln in calls) == layers
        assert len(calls) == 2 * layers and "ragged" not in text
        assert _kernels_by_scope(text) == {"grouped_experts": layers,
                                           "grouped_attention": layers}
        wide = {(1, 16384, 8, 128), (8, 1, 16384, 128), (16, 512, 16384),
                (1, 1, 16, 512, 16384), (1, 4608, 8, 128),
                (8, 1, 4608, 128), (16, 512, 4608),
                (1, 1, 16, 512, 4608)} & set(_hlo_by_shape(text))
        assert not wide, wide
    for rows in (nblocks * bs, (slots * ring + 1) * bs):
        layouts = {layout for _, layout in _hlo_by_shape(text)[(rows, 1024)]}
        assert layouts == {"1,0"}  # row-major
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + \
        m.output_size_in_bytes - m.alias_size_in_bytes
    print(program, "temp", m.temp_size_in_bytes / 1e9, "total", total / 1e9)
    assert m.temp_size_in_bytes < 1536 << 20
    assert total < 15.75e9 - 2.15e9 - 0.5e9, total


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_granite_hybrid_cell_programs_compile_inside_one_chip(program,
                                                              one_chip,
                                                              native):
    """`granite-4.0-h-micro.serve.chatrate.decode` / `.prefill` at the
    cell's shapes (all 40 layers at published widths in bf16, 64 slots,
    8,193 blocks of 16 rows for the 4 attention layers, a float32 state
    `[64, 64, 64, 128]` and `[64, 3, 4352]` convolution inputs for each
    of the 36 state-space layers, chunk 512): `decode`'s custom calls
    are the 36 state-space layers' `ssm_step_live` kernels and the 4
    attention layers' walks of the live blocks — no slot's whole table is
    re-laid as `[64, 2048, 8, 64]` — `prefill` has none (a chunk's
    grouped rows go to `jax.numpy`, the scan is
    `jax.numpy`), every state enters and leaves under its own shape —
    updated in place, not copied beside itself: the kernel's state is
    aliased input to output, and no temporary is as large as one layer's
    — and weights, rows, state and temporaries fit the chip's 15.75 GB
    with room for the check's 0.82 GB of reference logits and its
    float32 layer."""
    from deepspeed_tpu.models import GraniteHybrid, GraniteHybridConfig
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule

    slots, bs, nblocks, chunk, seq = 64, 16, 8193, 512, 2048
    model = GraniteHybrid(GraniteHybridConfig(
        max_seq_len=seq, param_dtype=jnp.bfloat16))
    spec, cfg = model.layer_spec(), model.config
    width = seq // bs
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks,
                          table_width=width)
    progs = ServeProgramBuilder(model, sched).build()

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert abs(held - 6.383e9) < 0.001e9
    rows = on((nblocks * bs, 512), jnp.bfloat16)
    state = (on((slots, 64, 64, 128), jnp.float32),
             on((slots, 3, 4352), jnp.bfloat16))
    caches = [state if spec.mixer_of(i) == "ssm" else (rows, rows)
              for i in range(cfg.num_layers)]
    nbytes = lambda c: sum(a.size * a.dtype.itemsize for a in c)
    assert abs(sum(nbytes(c) for c in caches if c is state) - 4.89e9) < 0.01e9
    assert abs(sum(nbytes(c) for c in caches if c is not state)
               - 1.07e9) < 0.01e9
    if program == "decode":
        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, width), jnp.int32),
                on((slots,), jnp.float32), on((slots,), jnp.int32),
                on((slots,), jnp.uint32))
    else:   # behind the table's entries: the slot
        args = (on((1, chunk), jnp.int32), on((), jnp.int32),
                on((), jnp.int32), on((width + 1,), jnp.int32),
                on((), jnp.float32), on((), jnp.int32), on((), jnp.uint32))
    compiled = progs[program].lower(params, caches, *args).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    if program == "decode":
        assert len(calls) == 40
        assert sum("ssm_step_live" in ln for ln in calls) == 36
        assert sum("paged_attention_walk" in ln for ln in calls) == 4
        assert (slots, seq, 8, 64) not in _hlo_by_shape(text)
        assert _kernels_by_scope(text) == {"ssm_step": 36,
                                           "grouped_attention": 4}
        # a layer's state enters, goes through the kernel and leaves:
        # nothing copies it, nothing else of its size is built
        # (the kernel takes it as [slots, 32, 128, 128]: the same bytes)
        by_shape = _hlo_by_shape(text)
        state_ops = {op for shape in ((slots, 64, 64, 128),
                                      (slots, 32, 128, 128))
                     for op, _ in by_shape[shape]}
        assert state_ops <= {"parameter", "custom-call", "get-tuple-element",
                             "bitcast"}, state_ops
    else:
        assert not calls
    m = compiled.memory_analysis()
    # all 72 state arrays and 8 pools are donated and aliased
    assert m.alias_size_in_bytes > 5.9e9
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + \
        m.output_size_in_bytes - m.alias_size_in_bytes
    print(program, "temp", m.temp_size_in_bytes / 1e9, "total", total / 1e9)
    assert m.temp_size_in_bytes < 1024 << 20
    assert total < 15.75e9 - 0.82e9 - 1.0e9, total


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_qwen3_next_cell_programs_compile_inside_one_chip(program, one_chip,
                                                          native):
    """`qwen3-next-80b-a3b-d12.serve.longchat.decode` / `.prefill` at the
    cell's shapes (layers 0-11 at published widths in bf16, 64 of 512
    experts, 18,992 rows of the vocabulary, 48 slots, 39,937 blocks of 16
    rows for the 3 full layers, a float32 state `[48, 32, 128, 128]` and
    `[48, 3, 8192]` convolution inputs for each of the 9 delta layers,
    chunk 512): `decode`'s custom calls are the 9 delta layers'
    `gdn_step_live` kernels, the 12 layers' walks of the touched experts
    and the 3 full layers' walks of the live blocks — no slot's whole
    table is re-laid as `[48, 13312, 2, 256]` — `prefill`'s the 3 full
    layers' walks of the request's live blocks (the chunked rule and the
    grouped products are `jax.numpy`); every state enters and leaves
    under its own shape, updated in place; and weights, rows, state and
    temporaries fit the chip's 15.75 GB with room for the check's 1.0 GB
    of reference logits and its float32 layer."""
    from deepspeed_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule

    slots, bs, nblocks, chunk, seq = 48, 16, 39937, 512, 13312
    model = Qwen3Next(Qwen3NextConfig(
        vocab_size=18992, max_seq_len=seq, num_layers=12, experts_held=64,
        param_dtype=jnp.bfloat16))
    spec, cfg = model.layer_spec(), model.config
    width = seq // bs
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks,
                          table_width=width)
    progs = ServeProgramBuilder(model, sched).build()

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    assert abs(held - 5.859e9) < 0.001e9
    rows = on((nblocks * bs, 512), jnp.bfloat16)
    state = (on((slots, 32, 128, 128), jnp.float32),
             on((slots, 3, 8192), jnp.bfloat16))
    caches = [state if spec.mixer_of(i) == "gdn" else (rows, rows)
              for i in range(cfg.num_layers)]
    nbytes = lambda c: sum(a.size * a.dtype.itemsize for a in c)
    assert abs(sum(nbytes(c) for c in caches if c is state) - 0.927e9) < 1e7
    assert abs(sum(nbytes(c) for c in caches if c is not state)
               - 3.926e9) < 1e7
    if program == "decode":
        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, width), jnp.int32),
                on((slots,), jnp.float32), on((slots,), jnp.int32),
                on((slots,), jnp.uint32))
    else:   # behind the table's entries: the slot
        args = (on((1, chunk), jnp.int32), on((), jnp.int32),
                on((), jnp.int32), on((width + 1,), jnp.int32),
                on((), jnp.float32), on((), jnp.int32), on((), jnp.uint32))
    compiled = progs[program].lower(params, caches, *args).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    if program == "decode":
        assert sum("gdn_step_live" in ln for ln in calls) == 9
        assert sum("paged_attention_walk" in ln for ln in calls) == 3
        assert sum("touched_experts" in ln for ln in calls) == 12
        assert (slots, seq, 2, 256) not in _hlo_by_shape(text)
        assert _kernels_by_scope(text) == {
            "gdn_step": 9, "grouped_attention": 3, "touched_experts": 12}
        state_ops = {op for op, _ in _hlo_by_shape(text)[
            (slots, 32, 128, 128)]}
        assert state_ops <= {"parameter", "custom-call", "get-tuple-element",
                             "bitcast"}, state_ops
    else:
        # beside them the 12 layers' routed products over slabs of
        # 1,280 of the chunk's 5,120 rows (PR 58), and none of XLA's own
        assert sum("paged_attention_prefill_walk" in ln for ln in calls) == 3
        assert sum("grouped_experts" in ln for ln in calls) == 12
        assert len(calls) == 15 and "ragged" not in text
        assert _kernels_by_scope(text) == {"grouped_attention": 3,
                                           "grouped_experts": 12}
    m = compiled.memory_analysis()
    # all 18 state arrays and 6 pools are donated and aliased
    assert m.alias_size_in_bytes > 4.8e9
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + \
        m.output_size_in_bytes - m.alias_size_in_bytes
    print(program, "temp", m.temp_size_in_bytes / 1e9, "total", total / 1e9)
    assert m.temp_size_in_bytes < 1024 << 20
    assert total < 15.75e9 - 1.0e9 - 1.0e9, total


_NEMOTRON_CELL = {}


def _nemotron_h_cell_compiled(program, one_chip):
    """The reasoning cell's `decode` or `prefill` program compiled for a
    described v5e at the cell's shapes, once a process: (compiled, slots,
    seq)."""
    if program in _NEMOTRON_CELL:
        return _NEMOTRON_CELL[program]
    from deepspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule

    slots, bs, nblocks, chunk, seq = 40, 16, 6401, 512, 2560
    model = NemotronH(NemotronHConfig(
        vocab_size=16384, max_seq_len=seq, experts_held=16,
        param_dtype=jnp.bfloat16))
    spec, cfg = model.layer_spec(), model.config
    width = seq // bs
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks,
                          table_width=width)
    progs = ServeProgramBuilder(model, sched).build()

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    # (A, D, the step's bias and the choosing bias are float32)
    assert held == 2 * 5_258_420_544 + 23 * (3 * 64 + 128) * 2
    rows = on((nblocks * bs, 256), jnp.bfloat16)
    state = (on((slots, 64, 64, 128), jnp.float32),
             on((slots, 3, 6144), jnp.bfloat16))
    caches = [{"ssm": state, "attention": (rows, rows), "none": ()}[
        spec.mixer_of(i)] for i in range(cfg.num_layers)]
    nbytes = lambda c: sum(a.size * a.dtype.itemsize for a in c)
    assert sum(nbytes(c) for c in caches if c is state) == \
        slots * 23 * 2_134_016
    assert abs(sum(nbytes(c) for c in caches if c is not state)
               - 0.629e9) < 1e6
    if program == "decode":
        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, width), jnp.int32),
                on((slots,), jnp.float32), on((slots,), jnp.int32),
                on((slots,), jnp.uint32))
    else:   # behind the table's entries: the slot
        args = (on((1, chunk), jnp.int32), on((), jnp.int32),
                on((), jnp.int32), on((width + 1,), jnp.int32),
                on((), jnp.float32), on((), jnp.int32), on((), jnp.uint32))
    compiled = progs[program].lower(params, caches, *args).compile()
    _NEMOTRON_CELL[program] = compiled, slots, seq
    return _NEMOTRON_CELL[program]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_nemotron_h_cell_programs_compile_inside_one_chip(program, one_chip,
                                                          native):
    """`nemotron-3-nano-30b-a3b-e16.serve.reasoning.decode` / `.prefill`
    at the cell's shapes (all 52 layers at published widths in bf16, 16
    of 128 experts, 16,384 rows of the vocabulary, 40 slots, 6,401 blocks
    of 16 rows for the 6 attention layers, a float32 state
    `[40, 64, 64, 128]` and `[40, 3, 6144]` convolution inputs for each
    of the 23 Mamba-2 layers, NOTHING for the 23 expert layers, chunk
    512): `decode`'s custom calls are the 23 mixers' `ssm_step_live`
    kernels (8 groups of B and C), the 23 expert layers' walks of the
    touched experts (two matrices of the whole width 1,856) and the 6
    attention layers' walks of the live blocks; `prefill`'s the 6 walks
    of the request's live blocks and the 23 slab products, none of
    XLA's own grouped products; every state enters and leaves under its
    own shape, updated in place; and weights, rows, state and
    temporaries fit the chip's 15.75 GB with room for the check's
    0.17 GB of reference logits and its float32 layer."""
    compiled, slots, seq = _nemotron_h_cell_compiled(program, one_chip)
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    if program == "decode":
        assert sum("ssm_step_live" in ln for ln in calls) == 23
        assert sum("paged_attention_walk" in ln for ln in calls) == 6
        assert sum("touched_experts" in ln for ln in calls) == 23
        assert (slots, seq, 2, 128) not in _hlo_by_shape(text)
        assert _kernels_by_scope(text) == {
            "ssm_step": 23, "grouped_attention": 6, "touched_experts": 23}
        by_shape = _hlo_by_shape(text)
        state_ops = {op for shape in ((slots, 64, 64, 128),
                                      (slots, 32, 128, 128))
                     for op, _ in by_shape[shape]}
        assert state_ops <= {"parameter", "custom-call", "get-tuple-element",
                             "bitcast"}, state_ops
    else:
        assert sum("paged_attention_prefill_walk" in ln for ln in calls) == 6
        assert sum("grouped_experts" in ln for ln in calls) == 23
        assert len(calls) == 29 and "ragged" not in text
        assert _kernels_by_scope(text) == {"grouped_attention": 6,
                                           "grouped_experts": 23}
    m = compiled.memory_analysis()
    # all 46 state arrays and 12 pools are donated and aliased
    assert m.alias_size_in_bytes > 2.5e9
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + \
        m.output_size_in_bytes - m.alias_size_in_bytes
    print(program, "temp", m.temp_size_in_bytes / 1e9, "total", total / 1e9)
    assert m.temp_size_in_bytes < 1024 << 20
    assert total < 15.75e9 - 0.17e9 - 1.0e9, total


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_nemotron_h_cell_programs_copy_no_experts_matrix(program, one_chip,
                                                         native):
    """Nothing in the cell's programs makes a second array the size of a
    layer's held experts' matrix: an expert's 1,856 columns are not whole
    128-lane tiles, so the chip keeps `up` `[16, 2688, 1856]` with D on
    the lanes, and a Mosaic operand asked for as written would be handed
    a copy of all 16 experts' matrix in every expert layer of every call
    (12 ms a decode step on the chip: PERF.md section 6, PR 61).  Both
    kernels take `up` turned (`moe_kernels._turned`), which is a bitcast
    of the same bytes; this holds the compiler to that, whatever layout
    it picks."""
    compiled, _, _ = _nemotron_h_cell_compiled(program, one_chip)
    by_shape = _hlo_by_shape(compiled.as_text())
    ops = {op for shape in ((16, 2688, 1856), (16, 1856, 2688))
           for op, _ in by_shape[shape]}
    assert ops and ops <= {"parameter", "bitcast", "get-tuple-element"}, ops


_LFM2_CELL = {}


def _lfm2_moe_cell_compiled(program, one_chip):
    """The assist cell's `decode` or `prefill` program compiled for a
    described v5e at the cell's shapes, once a process: (compiled, slots,
    seq)."""
    if program in _LFM2_CELL:
        return _LFM2_CELL[program]
    from deepspeed_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
    from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule

    slots, bs, nblocks, chunk, seq = 96, 16, 18433, 512, 3072
    model = Lfm2Moe(Lfm2MoeConfig(
        vocab_size=8192, max_seq_len=seq, experts_held=8,
        param_dtype=jnp.bfloat16))
    spec, cfg = model.layer_spec(), model.config
    width = seq // bs
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks,
                          table_width=width)
    progs = ServeProgramBuilder(model, sched).build()

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(params))
    # (the choosing bias is float32)
    assert held == 2 * 3_643_893_376 + 38 * 64 * 2
    rows = on((nblocks * bs, 512), jnp.bfloat16)
    kept = (on((slots, 2, 2048), jnp.bfloat16),)
    caches = [{"conv": kept, "attention": (rows, rows)}[spec.mixer_of(i)]
              for i in range(cfg.num_layers)]
    nbytes = lambda c: sum(a.size * a.dtype.itemsize for a in c)
    assert sum(nbytes(c) for c in caches if c is kept) == slots * 245_760
    assert abs(sum(nbytes(c) for c in caches if c is not kept)
               - 6.04e9) < 1e7
    if program == "decode":
        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, width), jnp.int32),
                on((slots,), jnp.float32), on((slots,), jnp.int32),
                on((slots,), jnp.uint32))
    else:   # behind the table's entries: the slot
        args = (on((1, chunk), jnp.int32), on((), jnp.int32),
                on((), jnp.int32), on((width + 1,), jnp.int32),
                on((), jnp.float32), on((), jnp.int32), on((), jnp.uint32))
    compiled = progs[program].lower(params, caches, *args).compile()
    _LFM2_CELL[program] = compiled, slots, seq
    return _LFM2_CELL[program]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_lfm2_moe_cell_programs_compile_inside_one_chip(program, one_chip,
                                                        native):
    """`lfm2-24b-a2b-e8.serve.assist.decode` / `.prefill` at the cell's
    shapes (all 40 layers at published widths in bf16, 8 of 64 experts,
    8,192 rows of the vocabulary, 96 slots, 18,433 blocks of 16 rows for
    the 10 attention layers, two kept rows `[96, 2, 2048]` for each of
    the 30 convolution layers, chunk 512): `decode`'s custom calls are
    the 38 routed layers' walks of the touched experts (three matrices,
    tiles of 1,536) and the 10 attention layers' walks of the live
    blocks — the convolution is `jax.numpy` and has no kernel;
    `prefill`'s the 38 slab products, none of XLA's own grouped products
    (a chunk's attention over heads of 64, narrower than a 128-lane
    tile, gathers); every kept array enters and leaves under its own
    shape, updated in place; and weights, rows and temporaries fit the
    chip's 15.75 GB with room for the check's 0.10 GB of reference
    logits and its float32 layer."""
    compiled, slots, seq = _lfm2_moe_cell_compiled(program, one_chip)
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    if program == "decode":
        assert sum("paged_attention_walk" in ln for ln in calls) == 10
        assert sum("touched_experts" in ln for ln in calls) == 38
        assert (slots, seq, 8, 64) not in _hlo_by_shape(text)
        assert _kernels_by_scope(text) == {
            "grouped_attention": 10, "touched_experts": 38}
    else:
        assert sum("grouped_experts" in ln for ln in calls) == 38
        assert "ragged" not in text
        assert _kernels_by_scope(text).get("grouped_experts") == 38
    m = compiled.memory_analysis()
    # all 30 kept arrays and 20 pools are donated and aliased
    assert m.alias_size_in_bytes > 6.0e9
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + \
        m.output_size_in_bytes - m.alias_size_in_bytes
    print(program, "temp", m.temp_size_in_bytes / 1e9, "total", total / 1e9)
    assert m.temp_size_in_bytes < 1024 << 20
    assert total < 15.75e9 - 0.10e9 - 1.0e9, total


def test_evabyte_phase_after_the_described_compiles(topo):
    """This file, then tests/test_chip_smoke.py::test_evabyte_phase_toy,
    in one process: the order in which the toy EvaByte run chose bytes
    0.2156 below the reference's best.  What the compiles leave behind
    is a different heap: a request's table then happened to lie where
    the CPU backend aliases a host array instead of copying it, and the
    prefill chunk that filled a window read the table after
    `close_window` had rewritten it (serving/kv_cache.py `extend`)."""
    from test_chip_smoke import test_evabyte_phase_toy

    test_evabyte_phase_toy()
