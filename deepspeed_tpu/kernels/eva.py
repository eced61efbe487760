"""EVA attention over the paged cache: the jnp oracle of the
`eva_attention` registry op (no Pallas kernel yet).

A query attends, in ONE softmax, to the exact rows of its open window up
to itself and to the summary rows of every closed window.  The block
table is `[window blocks | summary blocks]`: entry j < window/block_size
holds window offsets [j*bs, (j+1)*bs), entry window/block_size + m holds
the summaries of chunks [m*bs, (m+1)*bs).  What is visible follows from
the query's position alone — window offsets <= pos % window, summary
rows < (pos // window) * (window / chunk) — so stale rows, unallocated
(trash) entries and summaries of the open window's own chunks are masked
without the program being told.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def eva_attention_reference(q, ck, cv, tables, q_pos, *, window: int,
                            chunk: int, block_size: int):
    """q [B, T, H, Dh]; ck, cv flat caches [num_blocks*block_size, H,
    Dh]; tables [B, Wt] block ids; q_pos [B, T] -> [B, T, H, Dh] fp32.
    Blocks are gathered whole (block_size rows at a time)."""
    B, T, H, Dh = q.shape
    blocks = lambda c: c.reshape(-1, block_size, H, Dh)[tables] \
        .reshape(B, -1, H, Dh)
    k, v = blocks(ck), blocks(cv)
    kk = jnp.arange(k.shape[1])
    off = (q_pos % window)[..., None]
    n_vis = ((q_pos // window) * (window // chunk))[..., None]
    seen = jnp.where(kk < window, kk <= off, kk - window < n_vis)  # [B, T, K]
    scores = jnp.einsum("bthd,bkhd->bhtk", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32) * Dh ** -0.5
    scores = jnp.where(seen[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhtk,bkhd->bthd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
