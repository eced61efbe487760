"""Latent attention over a LEARNED selection of the cached rows, as the
serving programs run it (a layer spec whose `layer_indexers` mark layers
"full" or "shared"; models/glm_moe_dsa.py has the equations and the
pieces).  `serving/layers.py` imports this module when it first builds
such a block and not before.

A "full" layer's entry of the cache is a pair: the latent rows, and one
index key a token under the same block ids (serving/kv_cache.py).  Its
block writes both for the call's tokens, scores the call's queries
against the slot's cached keys through the block table (`dsa_index`),
chooses for every query the `index_topk` visible rows with the largest
scores — all of them while the query has no more — (`dsa_select`) and
attends those rows alone (`dsa_attend`).  What it chose rides on to the
"shared" layers behind it in the same call (`block(..., sel)`), which
own no index keys and score nothing.

Two shapes of call, two ways — told apart by the call's queries a
sequence, as latent attention's expanded and absorbed products are:

* a decode step (one query a slot): every index key of the slot's table
  gathered a block at a time, one stable sort over the table's width,
  and the chosen positions turned into pool rows once — the selection
  is a LIST of `index_topk` rows a slot.  Attention gathers exactly
  those rows and takes latent attention's absorbed products over them.
* a prefill chunk (`prefill_chunk` queries of one request): keys and
  rows are walked a tile of `KEY_TILE` positions at a time, as many
  tiles as the chunk's last position needs and no more — the trip count
  is data, the program one shape.  The threshold of every query is its
  k-th largest score, found without a sort
  (models/generation.py `kth_largest`), ties at it to the lower
  position; the selection is a MASK [queries, table positions].
  Attention expands a tile's rows through W_kv_b for all the chunk's
  queries and keeps a running softmax over the tiles, the mask applied
  to each — through the kernel registry (`masked_latent_attention`):
  where it picks the kernel (kernels/masked_latent.py) a head's scores
  of a tile stay in VMEM; `attend_tiles` below is the oracle every
  other backend runs.

A slot that is not running (`q_pos` < 0) sees no row, chooses none and
attends nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.deepseek_v2 import (attend_absorbed, latent_project,
                                  softmax_scale)
from ..models.evabyte import NEG_INF, matmul32
from ..models.glm_moe_dsa import index_project, index_scores, select_mask

# positions of one tile of a prefill chunk's walk over its request's rows
KEY_TILE = 1024


def key_tile(table_width: int, block_size: int) -> int:
    """The positions of one tile: whole blocks, a divisor of the
    table's width in positions, at most `KEY_TILE`."""
    blocks = next(b for b in range(min(table_width,
                                       max(KEY_TILE // block_size, 1)), 0, -1)
                  if table_width % b == 0)
    return blocks * block_size


def _tile_rows(pool, tables, i, blocks: int, block_size: int, width: int):
    """The rows of tile `i` of every sequence's table: pool [rows, lanes]
    through tables [B, W] -> [B, blocks * block_size, width]."""
    blk = jax.lax.dynamic_slice_in_dim(tables, i * blocks, blocks, axis=1)
    lanes = pool.shape[1]
    return pool.reshape(-1, block_size, lanes)[blk].reshape(
        tables.shape[0], blocks * block_size, lanes)[..., :width]


def _tiles_needed(q_pos, tile: int, most: int):
    """How many tiles hold a position some query of the call may see."""
    return jnp.clip((jnp.max(q_pos) + tile) // tile, 1, most)


def chunk_scores(q_i, w, keys_pool, tables, n_tiles, s, tile: int):
    """Index scores of a chunk's queries over their sequence's cached
    keys, tile by tile: -> [B, T, L] float32, -inf behind the tiles
    walked."""
    B, T = q_i.shape[:2]
    blocks = tile // s.block_size

    def one(i, buf):
        keys = _tile_rows(keys_pool, tables, i, blocks, s.block_size,
                          q_i.shape[-1])
        return jax.lax.dynamic_update_slice_in_dim(
            buf, index_scores(q_i, w, keys), i * tile, axis=2)

    return jax.lax.fori_loop(
        0, n_tiles, one,
        jnp.full((B, T, tables.shape[1] * s.block_size), -jnp.inf,
                 jnp.float32))


def attend_tiles(cfg, kv_b, q_nope, q_rope, pool, tables, mask, n_tiles, s,
                 tile: int):
    """Softmax attention of q_* [B, T, H, .] over the rows of `tables`
    that mask [B, T, L] lets each query see, a tile of rows at a time
    with a running maximum and sum; a tile's rows expanded through
    W_kv_b to per-head keys and values.  -> [B, T, H * v] float32."""
    B, T, H, nope = q_nope.shape
    rank, v = cfg.kv_lora_rank, cfg.v_head_dim
    width = rank + cfg.qk_rope_head_dim
    blocks = tile // s.block_size
    scale = softmax_scale(cfg.head_dim, cfg.yarn)

    def one(i, carry):
        m, l, acc = carry                     # [B, H, T], same, [B, H, T, v]
        rows = _tile_rows(pool, tables, i, blocks, s.block_size, width)
        kv = matmul32(rows[..., :rank], kv_b).astype(rows.dtype).reshape(
            B, tile, H, nope + v)
        sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :nope],
                         preferred_element_type=jnp.float32) +
              jnp.einsum("bqhd,bkd->bhqk", q_rope, rows[..., rank:],
                         preferred_element_type=jnp.float32)) * scale
        seen = jax.lax.dynamic_slice_in_dim(mask, i * tile, tile,
                                            axis=2)[:, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, sc, NEG_INF),
                                       axis=-1))
        pr = jnp.where(seen, jnp.exp(sc - m_new[..., None]), 0.0)
        keep = jnp.exp(m - m_new)
        acc = acc * keep[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", pr.astype(rows.dtype), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return m_new, l * keep + jnp.sum(pr, axis=-1), acc

    _, l, acc = jax.lax.fori_loop(
        0, n_tiles, one,
        (jnp.full((B, H, T), NEG_INF, jnp.float32),
         jnp.zeros((B, H, T), jnp.float32),
         jnp.zeros((B, H, T, v), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).reshape(B, T, H * v)


def masked_info(cfg, q_nope, q_rope, pool, kv_b, tile: int) -> dict:
    """What the kernel registry may look at to choose how a prefill
    chunk attends its selection: the call's sequences and queries, a
    head's sizes, the tile, the rows' and the weights' dtype."""
    B, T, _, nope = q_nope.shape
    return {"batch": B, "q_len": T, "tile": tile,
            "rank": cfg.kv_lora_rank, "nope": nope,
            "rope": q_rope.shape[-1], "v": cfg.v_head_dim,
            "kv_itemsize": jnp.dtype(pool.dtype).itemsize,
            "w_itemsize": jnp.dtype(kv_b.dtype).itemsize}


def select_step(scores, q_pos, tables, topk: int, block_size: int):
    """A decode step's selection: scores [B, L] of each slot's one query
    at q_pos [B] -> (pool rows [B, K], chosen [B, K] bool), K =
    min(topk, L): the visible positions with the largest scores, lower
    positions first among equals, as rows of the pool."""
    L = scores.shape[1]
    seen = jnp.arange(L)[None, :] <= q_pos[:, None]
    # one stable sort of the negated scores beside the positions: equal
    # scores keep the lower position first (0.24 ms at [8, 24576] on a
    # v5e where `lax.top_k` of 2,048 takes 0.44: PERF.md, PR 54)
    low, at = jax.lax.sort_key_val(
        -jnp.where(seen, scores, -jnp.inf),
        jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), scores.shape))
    K = min(topk, L)
    at, chosen = at[:, :K], low[:, :K] < jnp.inf
    blk = jnp.take_along_axis(tables, at // block_size, axis=1)
    return jnp.where(chosen, blk * block_size + at % block_size, 0), chosen


def sparse_latent_attend(spec, cfg, p, h, kv, addr, s, layer: int, sel,
                         write):
    """The attention branch of block `layer`: queries, the call's latent
    rows (and, in a "full" layer, its index keys) written through the
    table by `write` (layers.py `_kv_write`); the selection made here
    or taken from `sel`; attention over the chosen rows; output
    projection.  -> (float32 [B, T, D], the layer's cache entry, the
    selection as the next layer takes it)."""
    B, T, _ = h.shape
    pool = kv[0]
    q_nope, q_rope, rows, c_q = latent_project(
        cfg, p, h, addr.q_pos, pool.dtype, with_cq=True)
    pool = write(pool, addr.write_idx, rows.reshape(B * T, 1, -1), "dense")
    step = T == 1
    tile = key_tile(s.table_width, s.block_size)
    n_tiles = None if step else _tiles_needed(
        addr.q_pos, tile, s.table_width * s.block_size // tile)
    if spec.indexer_of(layer) == "full":
        keys = kv[1]
        with jax.named_scope("dsa_index"):
            q_i, k_i, w = index_project(cfg, p["indexer"], h, c_q,
                                        addr.q_pos, keys.dtype)
            keys = write(keys, addr.write_idx, k_i.reshape(B * T, 1, -1),
                         "dense")
            if step:
                scores = index_scores(q_i, w, _tile_rows(
                    keys, addr.tables, 0, s.table_width, s.block_size,
                    q_i.shape[-1]))
            else:
                scores = chunk_scores(q_i, w, keys, addr.tables, n_tiles, s,
                                      tile)
        with jax.named_scope("dsa_select"):
            if step:
                sel = select_step(scores[:, 0], addr.q_pos[:, 0],
                                  addr.tables, spec.index_topk, s.block_size)
            else:
                seen = jnp.arange(scores.shape[-1])[None, None, :] <= \
                    addr.q_pos[:, :, None]
                sel = select_mask(scores, seen, spec.index_topk)
        kv = (pool, keys)
    else:
        kv = (pool,)
    with jax.named_scope("dsa_attend"):
        if step:
            at, chosen = sel
            held = pool[at][..., :rows.shape[-1]]           # [B, K, width]
            out = attend_absorbed(cfg, p["kv_b"], q_nope, q_rope, held,
                                  chosen[:, None, :])
        else:
            from ..kernels import registry

            out = registry.dispatch(
                "masked_latent_attention", cfg, p["kv_b"], q_nope, q_rope,
                pool, addr.tables, sel, n_tiles, s, tile,
                info=masked_info(cfg, q_nope, q_rope, pool, p["kv_b"], tile))
    return matmul32(out, p["o"]), kv, sel
