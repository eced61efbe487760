"""EVA attention over the paged cache: the jnp oracle of the
`eva_attention` registry op, and its Pallas kernel for a decode step.

A query attends, in ONE softmax, to the exact rows of its open window up
to itself and to the summary rows of every closed window.  The block
table is `[window blocks | summary blocks]`: entry j < window/block_size
holds window offsets [j*bs, (j+1)*bs), entry window/block_size + m holds
the summaries of chunks [m*bs, (m+1)*bs).  What is visible follows from
the query's position alone — window offsets <= pos % window, summary
rows < (pos // window) * (window / chunk) — so stale rows, unallocated
(trash) entries and summaries of the open window's own chunks are masked
without the program being told.

Both kinds of row lie in the one pool, `[rows, pool_width(H, Dh)]`
(serving/kv_cache.py): a token's, or a chunk summary's, heads side by
side in one row, a block `block_size` consecutive rows.

The oracle (`eva_attention_reference`) gathers every entry of every
slot's table whatever the query needs — 3,072 rows a slot at EvaByte's
sizes — and masks.  It stays for prefill, for every backend but the
TPU, and as the kernel's correctness contract.

The kernel (`eva_attention_pallas`, `q_len` <= 8) is kernels/paged.py's
walk — one program a slot, the pool left in HBM, live blocks copied a
tile at a time, all heads a tile through block-diagonal queries, online
softmax in float32 — over the two runs of table entries that are live
for the slot's position: window entries 0 .. (pos % window) // bs, then
summary entries window/bs .. window/bs + ceil(n_vis / bs) - 1.  A slot in
its first window walks no summary block, a slot whose position is
negative (not running) walks nothing and returns zeros.  Operands enter
the MXU as the cache holds them and every sum is float32, as in the
oracle: only the order of sums and the online form of the softmax
differ.  Out: float32 (the oracle's contract; EvaByte's residual adds
are float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import pallas_backend

NEG_INF = -1e30


def live_blocks(pos, window: int, chunk: int, block_size: int):
    """(window blocks, summary blocks) of its table that a query at
    position `pos` >= 0 reads: what the kernel walks.  Ints or arrays."""
    return (pos % window // block_size + 1,
            -(-(pos // window * (window // chunk)) // block_size))


def eva_attention_reference(q, ck, cv, tables, q_pos, *, window: int,
                            chunk: int, block_size: int):
    """q [B, T, H, Dh]; ck, cv the pool [num_blocks*block_size,
    pool_width(H, Dh)]; tables [B, Wt] block ids; q_pos [B, T] ->
    [B, T, H, Dh] fp32.  Blocks are gathered whole (block_size rows at a
    time)."""
    B, T, H, Dh = q.shape
    rows = lambda c: c.reshape(-1, block_size, c.shape[-1])[tables].reshape(
        B, -1, c.shape[-1])
    # keys as [H, Dh, K], from the rows transposed as a matrix: split
    # into heads first (`bkhd`), the chip's compiler changes K's layout
    # inside the scores' product, which then runs 2.7 x slower and apart
    # from the softmax (PERF.md §6, PR 32)
    k = jnp.swapaxes(rows(ck)[..., :H * Dh], 1, 2).reshape(B, H, Dh, -1)
    v = rows(cv)[..., :H * Dh].reshape(B, -1, H, Dh)
    kk = jnp.arange(v.shape[1])
    off = (q_pos % window)[..., None]
    n_vis = ((q_pos // window) * (window // chunk))[..., None]
    seen = jnp.where(kk < window, kk <= off, kk - window < n_vis)  # [B, T, K]
    scores = jnp.einsum("bthd,bhdk->bhtk", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32) * Dh ** -0.5
    scores = jnp.where(seen[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhtk,bkhd->bthd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def eva_attention_pallas(q, ck, cv, tables, q_pos, *, window: int,
                         chunk: int, block_size: int):
    """Drop-in for `eva_attention_reference` (tolerance parity): the
    paged walk over a slot's live window and summary blocks."""
    from .paged import _walk

    return _walk(q.astype(ck.dtype), ck, cv, tables, q_pos,
                 kv_mode="dense", block_size=int(block_size),
                 window=int(window), chunk=int(chunk),
                 interpret=pallas_backend.interpret())
