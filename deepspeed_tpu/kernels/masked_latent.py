"""A prefill chunk's attention over a learned selection of latent rows,
the scores kept on the chip (serving/sparse.py `attend_tiles` is its
oracle, and has the mathematics).

The oracle walks the request's rows a tile of `tile` positions at a
time: it expands the tile's latent rows through W_kv_b to every head's
keys and values, scores the chunk's queries against them, masks by the
selection and keeps a running softmax — in `jax.numpy`, so every tile's
scores `[H, T, tile]` float32 go to HBM and come back five times (the
scores, their maximum, the probabilities, their sums, their cast).  Here
one program a head holds that head's `[T, tile]` scores in VMEM and
writes none of them: the grid runs over the heads, and inside a program
a loop walks the `n_tiles` tiles the chunk's last position needs — the
trip count is data, an operand in scalar prefetch, as it is the oracle's
loop bound — with the next tile's rows and mask in flight while this
one is multiplied (two buffers, one async copy each a tile).

What the call lays out before the kernel, in `jax.numpy`, so that every
slice inside it starts on a lane tile whatever the head sizes:

* the request's rows, gathered through its table a tile at a time for
  the `n_tiles` tiles walked (nothing behind them is read) as
  `[latent c | zeros, nope wide | rotated key]`: the last `nope + rope`
  lanes are the rotated key where a head's key has it;
* W_kv_b by head, `[H, rank, nope | zeros, rope wide | v]`: a head's
  expansion `c @ W` comes out as `[key without its rotated part | v]`,
  the zeros' columns exactly 0, and adding the rows' last lanes to its
  first `nope + rope` makes the whole key — each lane is one of the two
  plus zero, so the rounding to the rows' dtype is the oracle's;
* the queries by head, `[H, T, nope | rope]`: one product over
  `nope + rope` is the oracle's two, summed in float32;
* the selection as int8 (one cast a layer).

A tile's arithmetic is `attend_tiles`' line for line: float32 sums,
the expansion and the probabilities rounded to the rows' dtype before
they are multiplied, `where` before and after `exp`, the running sum
floored at 1e-30 — a wholly masked tile leaves maximum, sum and
accumulator as they were, and a query that sees nothing (a slot that is
not running) leaves with zeros.  Only the order of sums differs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.generation import NEG_INF
from ..ops import pallas_backend

# what the compiler may take beside the buffers `masked_vmem` counts (its
# own relayouts and the pipeline's bookkeeping)
_REST = 8 << 20
# the most the kernel may ask of a v5e's 128 MiB of VMEM
_VMEM = 100 << 20


def masked_vmem(q_len: int, tile: int, rank: int, qk: int, v: int,
                itemsize: int) -> int:
    """Bytes of VMEM a program of the walk needs: a tile's rows and mask
    in both buffers, a head's weights, queries and result in both
    pipeline buffers, the accumulator with `m` and `l` a lane tile wide,
    and a tile's temporaries — the expansion in float32 and rounded, the
    scores, the probabilities and their cast, the mask widened."""
    held = (2 * tile * (rank + qk) * itemsize + 2 * q_len * tile
            + 2 * rank * (qk + v) * itemsize + 2 * q_len * qk * itemsize
            + 3 * q_len * v * 4 + 2 * q_len * 128 * 4)
    temps = tile * (qk + v) * (4 + itemsize) + q_len * tile * (4 + 4 + 4 +
                                                              itemsize)
    return held + temps + _REST


def _kernel(nt_ref, q_ref, w_ref, rows_hbm, mask_hbm, o_ref, rbuf, mbuf, sem,
            m_s, l_s, acc, *, tile, rank, qk, scale):
    n_tiles = nt_ref[0]

    def copies(i, slot):
        at = pl.ds(pl.multiple_of(i * tile, tile), tile)
        return (pltpu.make_async_copy(rows_hbm.at[at], rbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(mask_hbm.at[:, at], mbuf.at[slot],
                                      sem.at[1, slot]))

    for cp in copies(0, 0):
        cp.start()
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc[...] = jnp.zeros_like(acc)

    def one(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_tiles)
        def _():
            for cp in copies(i + 1, 1 - slot):
                cp.start()

        for cp in copies(i, slot):
            cp.wait()
        rows = rbuf[slot]                                   # [tile, rank+qk]
        kv = jnp.dot(rows[:, :rank].astype(w_ref.dtype), w_ref[...],
                     preferred_element_type=jnp.float32)    # [tile, qk + v]
        k = (kv[:, :qk] + rows[:, rank:].astype(jnp.float32)).astype(
            rows.dtype)
        sc = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [T, tile]
        seen = mbuf[slot].astype(jnp.int32) != 0
        m = m_s[:, :1]
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, sc, NEG_INF), axis=1,
                                       keepdims=True))
        pr = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
        keep = jnp.exp(m - m_new)
        acc[...] = acc[...] * keep + jnp.dot(
            pr.astype(rows.dtype), kv[:, qk:].astype(rows.dtype),
            preferred_element_type=jnp.float32)
        l_new = l_s[:, :1] * keep + jnp.sum(pr, axis=1, keepdims=True)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
        return carry

    jax.lax.fori_loop(0, n_tiles, one, 0)
    o_ref[...] = acc[...] / jnp.maximum(l_s[:, :1], 1e-30)


def walked_rows(pool, tables, n_tiles, *, tile: int, block_size: int,
                rank: int, nope: int, rope: int):
    """The rows of the first `n_tiles` tiles of the one request's table
    [1, W], gathered from pool [rows, lanes] a tile at a time as `[c |
    zeros | rotated key]` -> [W * block_size, rank + nope + rope]; what
    lies behind the tiles walked is not read and not defined."""
    from ..serving.sparse import _tile_rows

    def one(i, buf):
        rows = _tile_rows(pool, tables, i, tile // block_size, block_size,
                          rank + rope)[0]
        rows = jnp.concatenate(
            [rows[:, :rank], jnp.zeros((tile, nope), rows.dtype),
             rows[:, rank:]], axis=1)
        return jax.lax.dynamic_update_slice_in_dim(buf, rows, i * tile,
                                                   axis=0)

    return jax.lax.fori_loop(
        0, n_tiles, one,
        jax.lax.empty((tables.shape[1] * block_size, rank + nope + rope),
                      pool.dtype))


def masked_latent_attention_pallas(cfg, kv_b, q_nope, q_rope, pool, tables,
                                   mask, n_tiles, s, tile: int):
    """Drop-in for serving/sparse.py `attend_tiles` at one request
    (tolerance parity): -> [1, T, H * v] float32."""
    from ..models.deepseek_v2 import softmax_scale

    # the cast stands in the caller's program, not in `_masked`: the
    # layers that share a selection then share its int8 copy
    return _masked(
        kv_b, q_nope, q_rope, pool, tables, mask.astype(jnp.int8), n_tiles,
        rank=int(cfg.kv_lora_rank), v=int(cfg.v_head_dim),
        block_size=int(s.block_size), tile=int(tile),
        scale=float(softmax_scale(cfg.head_dim, cfg.yarn)),
        interpret=pallas_backend.interpret())


@functools.partial(jax.jit, static_argnames=("rank", "v", "block_size", "tile",
                                             "scale", "interpret"))
def _masked(kv_b, q_nope, q_rope, pool, tables, mask, n_tiles, *, rank, v,
            block_size, tile, scale, interpret):
    """The call, a function of its own (a program that makes it in every
    layer lowers the kernel once): q_nope [1, T, H, nope] and q_rope
    [1, T, H, rope] over the rows that mask [1, T, L] int8 lets each
    query see among the first `n_tiles` tiles of tables [1, W]."""
    B, T, H, nope = q_nope.shape
    rope = q_rope.shape[-1]
    qk, L = nope + rope, mask.shape[-1]
    if B != 1 or L != tables.shape[1] * block_size or L % tile \
            or tile % block_size or kv_b.shape != (rank, H * (nope + v)):
        raise ValueError(
            f"masked latent attention kernel: one request's queries ({B} "
            f"given) over whole tiles of {tile} positions of its {L} (a "
            f"table of {tables.shape[1]} blocks of {block_size}), expanded "
            f"through W_kv_b [{rank}, {H} x ({nope} + {v})] (given "
            f"{kv_b.shape})")
    dt = pool.dtype
    w = kv_b.reshape(rank, H, nope + v)
    w = jnp.concatenate(
        [w[..., :nope], jnp.zeros((rank, H, rope), w.dtype), w[..., nope:]],
        axis=-1).transpose(1, 0, 2)
    q = jnp.concatenate([q_nope[0], q_rope[0]], axis=-1)
    rows = walked_rows(pool, tables.astype(jnp.int32), n_tiles, tile=tile,
                       block_size=block_size, rank=rank, nope=nope, rope=rope)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H,),
        in_specs=[
            pl.BlockSpec((None, T, qk), lambda h, nt: (h, 0, 0)),
            pl.BlockSpec((None, rank, qk + v), lambda h, nt: (h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((T, v), lambda h, nt: (0, h)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, rank + qk), dt),
            pltpu.VMEM((2, T, tile), jnp.int8),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((T, 128), jnp.float32),
            pltpu.VMEM((T, 128), jnp.float32),
            pltpu.VMEM((T, v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, rank=rank, qk=qk, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H * v), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,),
            vmem_limit_bytes=masked_vmem(T, tile, rank, qk, v, dt.itemsize)),
        name="masked_latent_attention",
        interpret=interpret,
    )(jnp.reshape(n_tiles, (1,)).astype(jnp.int32), q.transpose(1, 0, 2), w,
      rows, mask[0])
    return out[None]
