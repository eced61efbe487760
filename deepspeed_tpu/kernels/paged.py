"""Pallas decode-path paged attention (op 1): a walk of each slot's
block table over the pool as it lies, with the quantized-KV dequant
done on the fetched tile.

The pool (serving/kv_cache.py) keeps a layer's K and V as
`[num_blocks * block_size, pool_width(H, Dh)]`: one cache row is one
row of the array (`H * Dh` lanes, padded to whole 128-lane tiles), so a
block is `block_size` consecutive rows — one contiguous slab of whole
tiles, which the decode scatter writes in place and which both
implementations here read in place.

The jnp oracle (`paged_attention_reference`) is the expression
serving/layers.py's `_paged_attend` ran before there was a kernel:
gather the rows of every table entry, dequantize if the cache is
quantized, one fp32 einsum/softmax/einsum chain under the
`q_pos >= k_idx` mask.  Its cost is the table's whole width whatever a
slot holds; it stays for prefill (one request's rows; grouped rows of
whole-lane-tile heads walk there too, below), for every
backend but the TPU (all of tier-1 on CPU, where serving output stays
bit-identical to `generate()`), and as the kernel's correctness
contract.

The kernel (`paged_attention_pallas`, `q_len` <= 8: decode and verify)
runs one program per slot.  The block table and the query positions
ride scalar prefetch; a slot's length is its last query position + 1,
and a slot whose positions are negative is idle.  The pool stays in
HBM: the program copies the slot's LIVE blocks, a tile of several
blocks at a time, into a double-buffered VMEM tile (one async copy a
block, the next tile's in flight while this one is multiplied), in a
loop that ends at the slot's last live block — an idle slot and the
dead tail of a short one cost no copy and no loop step.

All heads share a tile without a reshape: the slot's queries arrive
block-diagonal, `[T * Hp, H * Dh]` with row (t, h) holding head h's
`Dh` values in that head's lanes and zeros elsewhere (built outside,
`Hp` = H rounded up to the sublane tile), so `Qbd @ tile^T` is every
head's scores `[T * Hp, rows]` in one MXU product — the zero lanes
contribute exactly 0.  Online softmax along the tile's rows in fp32 (the
flash_attention accumulator), probabilities cast to the cache dtype,
`p @ Vtile` accumulated in fp32 as `[T * Hp, H * Dh]`; at the end row
(t, h) keeps only head h's lanes and the rows of one t sum to the
output row.  Operands enter the MXU at the cache's dtype, every sum is
fp32: only the order of sums and the online form of the softmax differ
from the oracle.

Parity: tolerance-bounded, the attention-op contract.  Rows past a
slot's length are never fetched; rows of its last block past the query
are masked (`p = where(mask, ., 0)`), and the V tile is zeroed when a
program starts so that a masked row multiplies a finite number.  An
idle slot's output is zeros (the oracle's is the mean of the trash
block's values; the engine discards both).

The same walk serves chunk-summarised attention (kernels/eva.py, `_walk`
with `window` > 0): which table entries are live and which of their rows
a query sees then follow from the query's position — two runs, the open
window's blocks and the closed windows' summary blocks — and the output
is float32.  With `window` 0 the kernel's body is what it was.

And it serves grouped rows (`grouped_attention_pallas`; serving/layers.py
`_grouped_attend`, a full layer's decode step): a cache row holds
`kv_heads` heads, each read by `G = H / kv_heads` query heads, and the
call says so by its shapes.  The tile is the row as it lies,
`pool_width(kv_heads, Dh)` lanes; score row (t, h) carries query head
h's `Dh` values in the lanes of K/V head `h // G`, so the one product
still gives every head's scores, `G` rows a K/V head.  Rows that share
lanes cannot sum into one output row: the program writes its `[T * Hp,
lanes]` accumulator out, normalised, in float32, and the caller keeps
each score row's own `Dh` lanes (`_own_lanes`) -> `[B, T, H, Dh]`.  The
softmax scale is the caller's (Granite's `attention_multiplier` is not
`Dh ** -0.5`).  Liveness is the paged walk's own in a full layer — one
causal run from the table's first entry — and in a sliding layer's
decode or verify step a third rule (`_live_run`, `sliding` the layer's
window): position p lies in row p modulo the run the table names — the
slot's ring, or the table itself — so a query's window is consecutive
blocks modulo the table's width from the block of its lower bound, and
the walk copies those and no other (every slot's whole ring was
gathered before); walked row k holds position `base + k`, so the mask
stays linear.  That needs a run of `window + q_len - 1` rows (no row a
query sees overwritten by a newer lap) and, this walk being held to the
run's own count of blocks from a block's first row, `block_size` more
(kernels/registry.py refuses a shorter ring by what `grouped_info`
says).  At `G` = 1 nothing of this is traced.

And latent rows (`latent_attention_pallas`; serving/layers.py
`_latent_attend`, a decode step's absorbed products): a cache row is ONE
array a layer, key and value at once — [latent c | rotated key], one
"K/V head" read by every query head — which is the grouped tile at
`kv_heads` = 1, `G` = the query heads.  Score row (t, h) is head h's
absorbed query over the row's lanes; the weighted sum runs over the SAME
tile, so the call hands the kernel one operand and a block is copied
once; the caller keeps the accumulator's first `rank` lanes, the value.

A prefill chunk over grouped rows (`_prefill_walk`, what
`grouped_attention_pallas` runs past `STEP_QUERIES` queries; a chunk of
serving/layers.py `_grouped_attend`) is a kernel of its
own beside `_walk_kernel`, with the same liveness and the same online
softmax: ONE request, its table and `q_pos` in scalar prefetch, the grid
over tiles of `tq` query positions.  A program copies the request's
blocks from the table's first entry to the one its LAST position needs
(tiles of blocks, double-buffered, as above) and nothing behind it — a
padded tail's positions run past the table, and the run ends with it.
Under a window (`sliding`, the layer's) its liveness is the decode
walk's third rule at a tile of queries (`_sliding_run`, shared): from
the block of the tile's OLDEST lower bound, modulo the table — the
request's ring, or the table itself — to the block of its last
position.  A tile is copied block by block, so it lies in VMEM in
walked order whatever the ring's wrap: walked row k holds position
`base + k` and the mask is two bounds a query; the tiles every query
sees whole are now a run in the middle (masked / whole / masked).  A
ring of `window + T - 1` rows is enough — no row a query sees was
overwritten by the chunk's newest position — and one that short may
have ONE block that is its oldest and its newest at once, which the
walk copies at both ends and masks by position at each (the request's
whole ring was gathered before, whatever the position).
What differs is the product: a chunk is compute-bound, and block-diagonal
queries would multiply every K/V head's lanes for each query head —
`kv_heads` times the MXU work.  So the program regroups its queries once
to `[kv_heads, G * tq, Dh]` and multiplies K/V head n's `G * tq` rows
with that head's 128-lane slice of the tile (`Dh` a whole number of lane
tiles: narrower heads keep the gather); tiles every query of the program
sees whole skip the mask; the float32 accumulator leaves normalised, as
`[1, T, H * Dh]`, written once.  Not one body with the decode walk: that
one unrolls over <= 8 queries whose score rows fill one MXU tile, this
one tiles 512 of them over a grid and loops over K/V heads — the copies
(`tile_copies`) and the softmax's update are the shared idiom, the tile
and the loop nest are not.  The paged, latent and summarised rows keep
the oracle in prefill (ROADMAP S11's later cases).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import pallas_backend
from ..models.generation import NEG_INF
from ..serving.kv_cache import rows_for_tables

# what a program's double-buffered K and V tiles may take of VMEM, and
# what its accumulator and query block may (the chip's scoped default
# is 16 MiB; the compiler's own temporaries need the rest)
_TILE_BYTES = 6 << 20
_ACC_BYTES = 6 << 20
# rows a tile aims for: the scores of a tile are `[T * Hp, rows]`, so a
# multiple of the 128 lanes keeps them dense
_TILE_ROWS = 256
# the most queries a slot has in a decode or verify step, over which
# `_walk_kernel` unrolls; a call of more is a prefill chunk
STEP_QUERIES = 8
# a prefill chunk's walk (`_prefill_walk`): rows a K/V tile aims for,
# query positions a program may take (the largest that divides the chunk
# and fits), what its tiles may take of VMEM and what is left to the
# compiler beside them (v5e: 128 MiB, of which 16 are the default)
_PREFILL_TILE_ROWS = 512
_PREFILL_QUERIES = (64, 32, 16, 8)
_PREFILL_TILE_BYTES = 40 << 20
_PREFILL_REST = 16 << 20


def kv_read(c, rows, num_heads: int, head_dim: int,
            kv_mode: str = "dense"):
    """Gather cache rows `rows` [B, L] -> [B, L, H, Dh].  Dense reads
    come back at the cache dtype; quantized caches ((payload, scales)
    pairs) dequantize the gathered rows to fp32."""
    B, L = rows.shape
    if kv_mode == "dense":
        return c[rows][..., :num_heads * head_dim].reshape(
            B, L, num_heads, head_dim)
    from ..runtime.comm.quant import dequantize_rows

    payload, scales = c
    w = head_dim if kv_mode == "int8" else head_dim // 2
    return dequantize_rows(
        payload[rows][..., :num_heads * w].reshape(B, L, num_heads, w),
        scales[rows], kv_mode)


def paged_attention_reference(q, ck, cv, tables, q_pos, *,
                              kv_mode: str = "dense",
                              block_size: int):
    """The `_paged_attend` attention core: q [B, T, H, Dh], the pool
    `[rows, pool_width]` addressed through block tables [B, W], q_pos [B, T]
    absolute positions -> attn [B, T, H, Dh] (at the cache/dequant
    dtype)."""
    H, Dh = q.shape[2:]
    rows = rows_for_tables(tables, block_size)
    keys = kv_read(ck, rows, H, Dh, kv_mode)      # [B, L, H, Dh]
    vals = kv_read(cv, rows, H, Dh, kv_mode)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) * (Dh ** -0.5)
    L = rows.shape[1]
    k_idx = jnp.arange(L)[None, None, :]
    mask = q_pos[:, :, None] >= k_idx            # [B, T, L]
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vals.dtype), vals)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def score_rows(q_len: int, num_heads: int) -> int:
    """Rows of a slot's scores: every (query, head) pair, the heads of
    one query padded to whole sublane tiles at any operand dtype."""
    return q_len * _round_up(num_heads, 16)


def tile_blocks(block_size: int, table_width: int, row_bytes: int,
                q_len: int, num_heads: int, width: int) -> int:
    """Blocks a tile holds: about `_TILE_ROWS` rows, no more than the
    table has, and K and V double-buffered inside `_TILE_BYTES`.
    0: the shapes do not fit VMEM — not even one block does, or the
    fp32 accumulator `[score_rows, width]` with the query block."""
    if score_rows(q_len, num_heads) * width * (4 + 2 * 4) > _ACC_BYTES:
        return 0
    fit = _TILE_BYTES // (4 * block_size * row_bytes)
    return min(max(1, _TILE_ROWS // block_size), table_width, fit)


def _decode_nibbles(raw, full):
    """uint8 [rows, full // 2] -> int8 codes [rows, full] (quant.py's
    low-nibble-first two's-complement decode)."""
    lo = (raw & jnp.uint8(0x0F)).astype(jnp.int8)
    hi = ((raw >> 4) & jnp.uint8(0x0F)).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(raw.shape[0], full)


def _f16_bits_to_f32(bits):
    """uint16 fp16 bit patterns (non-negative: they are scales) -> the
    fp32 values, in integer ops: the chip loads no fp16 vectors
    ("Invalid vector type for load")."""
    b = bits.astype(jnp.uint32)
    exp, man = (b >> 10) & jnp.uint32(0x1F), b & jnp.uint32(0x3FF)
    # normals re-bias the exponent (15 -> 127); exponent 31 is inf/nan
    exp32 = jnp.where(exp == 31, jnp.uint32(255), exp + jnp.uint32(112))
    normal = jax.lax.bitcast_convert_type((exp32 << 23) | (man << 13),
                                          jnp.float32)
    sub = man.astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return jnp.where(exp == 0, sub, normal)


def _head_lanes(rows: int, H: int, Dh: int, width: int):
    """bool [rows, width]: lane belongs to head (row index); rows past
    H and lanes past H * Dh belong to none."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    return (lane >= head * Dh) & (lane < (head + 1) * Dh) & (head < H)


def _tile_kv(buf, sbuf, slot, kv_mode, H, Dh, marker):
    """The fetched tile `[rows, H * Dh]` as the MXU takes it: dense as
    it lies; quantized dequantized here — codes times the (row, head)
    scale spread over the head's lanes — to fp32."""
    raw = buf[slot].reshape(-1, buf.shape[-1])
    if kv_mode == "dense":
        return raw
    if kv_mode == "int4":
        codes = _decode_nibbles(raw[:, :H * Dh // 2], H * Dh)
    else:
        codes = raw[:, :H * Dh].astype(jnp.int8)
    scales = _f16_bits_to_f32(sbuf[slot].reshape(-1, H))     # (rows, H)
    spread = jnp.dot(scales,
                     _head_lanes(H, H, Dh, H * Dh).astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    # compared as fp32: the chip has no int8 vector comparison
    codes = codes.astype(jnp.float32)
    return jnp.where(codes == marker, jnp.float32(jnp.nan), codes * spread)


def _tile_copies(pairs, sem, entry, n_blocks, KB, tile, slot, go):
    """`go` (start or wait) every live block of `tile`: one copy a block
    and (pool, buffer) pair, a block being `bs` whole rows of the pool,
    the one `entry(blk)` names."""
    first = tile * KB

    def one(blk, carry):
        for n, (src, dst) in enumerate(pairs):
            go(pltpu.make_async_copy(
                src.at[entry(blk)], dst.at[slot, blk - first],
                sem.at[n, slot]))
        return carry

    jax.lax.fori_loop(first, jnp.minimum(first + KB, n_blocks), one, 0)


def _sliding_run(low, last, *, bs, W, most):
    """A sliding run's walk, from the oldest lower bound `low` among its
    queries and their newest position `last` (negative: nothing to walk):
    consecutive blocks modulo the run's `W` entries from the one that
    holds `low`, `most` of them at most.  -> (base, n_blocks, entry):
    walked row k holds position `base + k`, walked block `blk` is entry
    `entry(blk)` of the table."""
    first = low // bs
    base = first * bs
    n_blocks = jnp.where(last >= 0,
                         jnp.minimum(last // bs - first + 1, most), 0)
    return base, n_blocks, lambda blk: jax.lax.rem(first + blk, W)


def _live_run(qp, b, *, T, bs, W, window, chunk, sliding):
    """A slot's liveness, from its query positions and what the call says
    of its rows: which entries of its table the walk copies and which of
    their rows a query sees.  Three rules — one causal run from the
    table's first entry; summarised windows' two runs (`window`, `chunk`:
    kernels/eva.py); a sliding layer's modular run (`sliding`: the
    layer's window) — and all the loop below knows of them (ROADMAP D11
    would lift this to the caller, the runs as data).
    -> (n_blocks, entry, visible): the walk is `n_blocks` long, its
    block `blk` is table entry `entry(blk)`, and `visible(by_query)` is
    the mask of a tile's walked row indices, `by_query(value)` laying a
    scalar a query out over that query's score rows."""
    # T is tiny (1 decode, draft + 1 verify): the scalar position reads
    # unroll.  The slot's length is its last query's position + 1
    last = qp[b, 0]
    for t in range(1, T):
        last = jnp.maximum(last, qp[b, t])
    if sliding:
        # a sliding layer's rows (serving/layers.py `_grouped_attend`):
        # position p lies in row p % (W * bs) of the run the table names
        # — a ring, or the table itself, which no position laps — and a
        # query at p sees positions max(0, p - sliding + 1) .. p.  Those
        # are consecutive blocks modulo W from the one that holds the
        # oldest query's lower bound, and walked row k holds position
        # `base + k`: the run is long enough that no row at or below
        # `last` was overwritten by a newer lap (kernels/registry.py
        # holds it to that), so the mask is linear, each query's own
        # bounds; rows past `last` are masked as the causal run's are
        lows = [jnp.maximum(jnp.where(qp[b, t] >= 0, qp[b, t], last)
                            - sliding + 1, 0) for t in range(T)]
        base, n_blocks, entry = _sliding_run(
            functools.reduce(jnp.minimum, lows), last, bs=bs, W=W, most=W)

        def visible(by_query):
            qlow = by_query(lambda t: lows[t] - base)
            qrow = by_query(lambda t: qp[b, t] - base)
            return lambda kidx: (kidx >= qlow) & (kidx <= qrow)
    elif not window:
        n_blocks = jnp.clip((last + bs) // bs, 0, W)
        entry = lambda blk: blk

        def visible(by_query):
            qrow = by_query(lambda t: qp[b, t])
            return lambda kidx: qrow >= kidx
    else:
        # summarised windows (kernels/eva.py): the live blocks are two
        # runs of the table, walked as one — the open window's, entries
        # 0 .. n_win - 1, then the closed windows' summary blocks from
        # entry window // bs on.  A query sees window rows up to its
        # offset and summary rows below its count; several queries walk
        # the longest run of each kind and mask their own
        offs = [jnp.maximum(qp[b, t], 0) % window for t in range(T)]
        sums = [jnp.maximum(qp[b, t], 0) // window * (window // chunk)
                for t in range(T)]
        n_win = functools.reduce(jnp.maximum, offs) // bs + 1
        n_sum = jnp.minimum((functools.reduce(jnp.maximum, sums) + bs - 1)
                            // bs, W - window // bs)
        sums = [jnp.minimum(n, n_sum * bs) for n in sums]
        n_blocks = jnp.where(last >= 0, n_win + n_sum, 0)
        entry = lambda blk: jnp.where(blk < n_win, blk,
                                      blk - n_win + window // bs)

        def visible(by_query):
            qoff = by_query(lambda t: offs[t])
            qsum = by_query(lambda t: sums[t])
            # (no select between masks: Mosaic has no i1 `select_n`)
            return lambda kidx: (
                ((kidx < n_win * bs) & (kidx <= qoff)) |
                ((kidx >= n_win * bs) & (kidx - n_win * bs < qsum)))
    return n_blocks, entry, visible


def _walk_kernel(tbl, qp, q_ref, *rest, scale, bs, W, KB, T, H, Hp, Dh,
                 kv_mode, marker, window=0, chunk=0, G=1, sliding=0):
    if kv_mode == "dense" and len(rest) == 7:
        # one array is key and value (a latent row): the one tile is
        # multiplied twice and a block copied once
        k_hbm, o_ref, kbuf, sem, acc, m_s, l_s = rest
        vbuf, pairs = kbuf, ((k_hbm, kbuf),)
        ksbuf = vsbuf = None
    elif kv_mode == "dense":
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, acc, m_s, l_s = rest
        pairs = ((k_hbm, kbuf), (v_hbm, vbuf))
        ksbuf = vsbuf = None
    else:
        (k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref,
         kbuf, ksbuf, vbuf, vsbuf, sem, acc, m_s, l_s) = rest
        pairs = ((k_hbm, kbuf), (v_hbm, vbuf),
                 (ks_hbm, ksbuf), (vs_hbm, vsbuf))
    b = pl.program_id(0)
    C, TK = T * Hp, KB * bs
    n_blocks, entry, visible_of = _live_run(
        qp, b, T=T, bs=bs, W=W, window=window, chunk=chunk, sliding=sliding)
    n_tiles = (n_blocks + KB - 1) // KB
    tile_copies = functools.partial(
        _tile_copies, pairs, sem, lambda blk: tbl[b, entry(blk)], n_blocks,
        KB)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_tiles > 0)
    def _walk():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        # a masked row's probability is 0 and must meet a finite value
        vbuf[...] = jnp.zeros_like(vbuf)
        if vsbuf is not None:
            vsbuf[...] = jnp.zeros_like(vsbuf)
        tile_copies(0, 0, lambda cp: cp.start())
        row = jax.lax.broadcasted_iota(jnp.int32, (C, TK), 0)

        def by_query(value):
            """(C, TK): row (t, h) holds query t's `value(t)`."""
            out = jnp.full((C, TK), -1, jnp.int32)
            for t in range(T):
                out = jnp.where((row >= t * Hp) & (row < (t + 1) * Hp),
                                value(t), out)
            return out

        visible = visible_of(by_query)
        q = q_ref[0]                                       # (C, H * Dh)

        def body(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                tile_copies(i + 1, 1 - slot, lambda cp: cp.start())

            tile_copies(i, slot, lambda cp: cp.wait())
            k = _tile_kv(kbuf, ksbuf, slot, kv_mode, H, Dh, marker)
            dt = jnp.promote_types(q.dtype, k.dtype)
            s = jax.lax.dot_general(
                q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (C, TK)
            kidx = i * TK + jax.lax.broadcasted_iota(jnp.int32, (C, TK), 1)
            mask = visible(kidx)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_s[:, :1]
            l_prev = l_s[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            v = _tile_kv(vbuf, vsbuf, slot, kv_mode, H, Dh, marker)
            acc[...] = acc[...] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
            l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
            return carry

        jax.lax.fori_loop(0, n_tiles, body, 0)
        if G > 1:
            # grouped rows: G score rows share a K/V head's lanes, so the
            # rows of one t do not sum; each leaves whole and the caller
            # keeps its own lanes (`_own_lanes`)
            l = l_s[:, :1]
            o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)
            return
        # row (t, h) keeps head h's lanes; the rows of one t make the
        # output row
        own = _head_lanes(Hp, H, Dh, acc.shape[-1])
        for t in range(T):
            rows = slice(t * Hp, (t + 1) * Hp)
            l = l_s[rows, :1]
            out = acc[rows, :] / jnp.where(l == 0.0, 1.0, l)
            o_ref[0, t:t + 1, :] = jnp.sum(
                jnp.where(own, out, 0.0), axis=0,
                keepdims=True).astype(o_ref.dtype)


def _block_diagonal(q, Hp: int, width: int, G: int = 1):
    """q [B, T, H, Dh] -> [B, T * Hp, width]: row (t, h) holds head
    h's values in the lanes of the K/V head it reads, h // G, zeros
    elsewhere (in the rows that pad H up to Hp and the lanes that pad a
    row's heads up to the pool's width)."""
    B, T, H, Dh = q.shape
    if G == 1:   # as it stood: the G = 1 callers' programs keep their bytes
        flat = jnp.pad(q.reshape(B, T, 1, H * Dh),
                       ((0, 0),) * 3 + ((0, width - H * Dh),))
        own = (jnp.arange(width)[None, :] // Dh) == jnp.arange(Hp)[:, None]
        return jnp.where(own[None, None], flat, 0).reshape(B, T * Hp, width)
    KV = H // G
    # each query head's values under every K/V head, then its own kept
    rows = jnp.pad(jnp.tile(q, (1, 1, 1, KV)),
                   ((0, 0), (0, 0), (0, Hp - H), (0, width - KV * Dh)))
    own = (jnp.arange(width)[None, :] // Dh) == \
        (jnp.arange(Hp)[:, None] // G)
    return jnp.where(own[None, None], rows, 0).reshape(B, T * Hp, width)


def _own_lanes(out, T: int, H: int, G: int, Dh: int):
    """The grouped walk's rows [B, T * Hp, width] -> [B, T, H, Dh]: score
    row (t, h) keeps the lanes of K/V head h // G."""
    B, C, width = out.shape
    rows = out.reshape(B, T, C // T, width)
    return jnp.concatenate(
        [rows[:, :, n * G:(n + 1) * G, n * Dh:(n + 1) * Dh]
         for n in range(H // G)], axis=2)


def paged_attention_pallas(q, ck, cv, tables, q_pos, *,
                           kv_mode: str = "dense", block_size: int):
    """Drop-in for `paged_attention_reference` (tolerance parity)."""
    return _walk(q, ck, cv, tables, q_pos, kv_mode=kv_mode,
                 block_size=int(block_size),
                 interpret=pallas_backend.interpret())


def grouped_attention_pallas(q, ck, cv, tables, q_pos, *, kv_heads: int,
                             block_size: int, scale=None, window: int = 0,
                             newest=None):
    """Drop-in for serving/layers.py's `grouped_attention_reference`
    (tolerance parity): the walk at `G` = q's heads / `kv_heads` ->
    [B, T, H * Dh] float32.  A decode or verify step under a `window`
    walks the window's live blocks modulo the table it was handed — the
    slot's ring where `newest` is given, which is then the largest of
    the slot's `q_pos` (serving/layers.py `_address_grouped`) and read
    from them.  A prefill chunk (`T` past `STEP_QUERIES`, one request)
    walks the same two runs a tile of its queries a program: one causal
    run of the request's table, or under a `window` the blocks from the
    tile's oldest lower bound, modulo the table."""
    B, T, H, Dh = q.shape
    args = dict(block_size=int(block_size), kv_heads=int(kv_heads),
                scale=None if scale is None else float(scale),
                interpret=pallas_backend.interpret())
    if newest is not None and not window:
        raise ValueError(
            f"paged attention kernel: the rows of {T} queries' table are "
            f"a ring (`newest` given) under no window; the walk takes a "
            f"ring's rows by position behind a window's lower bound, and "
            f"one causal run from a table's first entry")
    if T > STEP_QUERIES:
        return _prefill_walk(q, ck, cv, tables, q_pos, sliding=int(window),
                             **args)
    return _walk(q, ck, cv, tables, q_pos, kv_mode="dense",
                 sliding=int(window), **args).reshape(B, T, H * Dh)


def prefill_tiles(q_len: int, num_heads: int, kv_heads: int, head_dim: int,
                  block_size: int, table_width: int, itemsize: int):
    """(query positions a program takes, blocks a K/V tile holds) of a
    prefill chunk's walk, or None where no tile of whole sublanes divides
    the chunk and fits `_PREFILL_TILE_BYTES`: the queries as they come
    and regrouped, the float32 accumulator and output block, `m` and `l`
    a lane tile wide, K and V double-buffered, and a K/V head's scores
    three times (scores, probabilities, their cast)."""
    lanes = num_heads * head_dim
    kb = min(max(1, _PREFILL_TILE_ROWS // block_size), table_width)
    rows = kb * block_size
    for tq in _PREFILL_QUERIES:
        if q_len % tq:
            continue
        held = (tq * lanes * (3 * itemsize + 3 * 4)
                + 2 * tq * num_heads * 128 * 4
                + 4 * rows * kv_heads * head_dim * itemsize
                + 3 * tq * (num_heads // kv_heads) * rows * 4)
        if held <= _PREFILL_TILE_BYTES:
            return tq, kb
    return None


def _prefill_kernel(tbl, qp, q_ref, pos_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                    sem, q_s, acc, m_s, l_s, *, scale, bs, W, KB, tq, KV, G,
                    Dh, sliding=0):
    """One program a tile of `tq` query positions of the one request:
    its blocks up to the one the tile's LAST position needs — from the
    table's first entry, or under a window (`sliding`) from the block of
    the tile's oldest lower bound, modulo the table — a tile of `KB` at a
    time; a K/V head's `G * tq` query rows against that head's lanes of
    the tile."""
    i = pl.program_id(0)
    TK, R = KB * bs, G * tq

    def span(t, c):
        p = qp[0, i * tq + t]
        return jnp.minimum(c[0], p), jnp.maximum(c[1], p)

    first, last = jax.lax.fori_loop(
        1, tq, span, (qp[0, i * tq], qp[0, i * tq]))
    if sliding:
        # the decode walk's third rule (`_live_run`) for a tile of
        # queries: a query at p sees positions max(0, p - sliding + 1) ..
        # p, which lie in consecutive blocks modulo W; the tile is copied
        # block by block, so walked row k holds position `base + k`
        # whatever the ring's wrap, and the mask is two bounds a query.
        # One block more than the run has may be walked: a run of just
        # `sliding + T - 1` rows whose oldest block is also its newest
        # (its first rows a lap ahead of its last) is copied at both ends
        # of the walk and masked by position at each
        base, n_blocks, entry = _sliding_run(
            jnp.maximum(first - sliding + 1, 0), last, bs=bs, W=W,
            most=W + 1)
    else:
        # a padded tail's positions run past the table: the run ends
        # with it
        n_blocks = jnp.clip((last + bs) // bs, 0, W)
        entry = lambda blk: blk
    n_tiles = (n_blocks + KB - 1) // KB
    # tiles every query of the program sees whole need no mask
    if sliding:
        # they lie between the newest query's lower bound and the oldest
        # query's position, where every block of theirs was copied:
        # masked / whole / masked
        n_cut = jnp.clip(
            (jnp.maximum(last - sliding + 1, 0) - base + TK - 1) // TK,
            0, n_tiles)
        n_whole = jnp.clip(
            jnp.minimum((first + 1 - base) // TK, n_blocks // KB),
            n_cut, n_tiles)
    else:
        n_whole = jnp.clip((first + 1) // TK, 0, n_tiles)
    tile_copies = functools.partial(
        _tile_copies, ((k_hbm, kbuf), (v_hbm, vbuf)), sem,
        lambda blk: tbl[0, entry(blk)], n_blocks, KB)

    @pl.when(n_tiles == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def head_lanes(h):
        """Query head h's (or K/V head h's) `Dh` lanes of a row."""
        return pl.ds(pl.multiple_of(h * Dh, 128), Dh)

    def per_head(fn):
        """`fn(n)` for every K/V head n, in a loop: unrolled, the body
        is traced and lowered `KV` times (seconds of every start-up)."""
        jax.lax.fori_loop(0, KV, lambda n, c: fn(n) or c, 0)

    @pl.when(n_tiles > 0)
    def _walk():
        # a masked row's probability is 0 and must meet a finite value
        vbuf[...] = jnp.zeros_like(vbuf)
        tile_copies(0, 0, lambda cp: cp.start())

        def regroup(n):
            # query head (n, g)'s rows under K/V head n: [KV, G * tq, Dh]
            for g in range(G):
                q_s[n, g * tq:(g + 1) * tq, :] = \
                    q_ref[0, :, head_lanes(n * G + g)]

        per_head(regroup)
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        qrow = jnp.concatenate([pos_ref[...]] * G, axis=0)       # (R, 1)
        if sliding:
            qlow = jnp.maximum(qrow - sliding + 1, 0) - base
            qrow = qrow - base

        def attend(tile, slot, masked):
            if masked:
                kidx = tile * TK + jax.lax.broadcasted_iota(
                    jnp.int32, (R, TK), 1)
                mask = qrow >= kidx
                if sliding:
                    mask = mask & (kidx >= qlow)

            def head(n):
                q = q_s[n]
                k = kbuf[slot, :, :, head_lanes(n)].reshape(TK, Dh)
                dt = jnp.promote_types(q.dtype, k.dtype)
                s = jax.lax.dot_general(
                    q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale   # (R, TK)
                if masked:
                    s = jnp.where(mask, s, NEG_INF)
                m_prev = m_s[n, :, :1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                if masked:
                    p = jnp.where(mask, p, 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_new = alpha * l_s[n, :, :1] + jnp.sum(p, axis=1,
                                                        keepdims=True)
                v = vbuf[slot, :, :, head_lanes(n)].reshape(TK, Dh)
                acc[n] = acc[n] * alpha + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                m_s[n] = jnp.broadcast_to(m_new, m_s.shape[1:])
                l_s[n] = jnp.broadcast_to(l_new, l_s.shape[1:])

            per_head(head)

        def body(masked, j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_tiles)
            def _():
                tile_copies(j + 1, 1 - slot, lambda cp: cp.start())

            tile_copies(j, slot, lambda cp: cp.wait())
            attend(j, slot, masked)
            return carry

        if sliding:
            jax.lax.fori_loop(0, n_cut, functools.partial(body, True), 0)
            jax.lax.fori_loop(n_cut, n_whole,
                              functools.partial(body, False), 0)
        else:
            jax.lax.fori_loop(0, n_whole, functools.partial(body, False), 0)
        jax.lax.fori_loop(n_whole, n_tiles, functools.partial(body, True), 0)

        def leave(n):
            for g in range(G):
                rows = slice(g * tq, (g + 1) * tq)
                l = l_s[n, rows, :1]
                o_ref[0, :, head_lanes(n * G + g)] = \
                    acc[n, rows, :] / jnp.where(l == 0.0, 1.0, l)

        per_head(leave)


@functools.partial(jax.jit, static_argnames=("kv_heads", "block_size",
                                             "scale", "interpret", "sliding"))
def _prefill_walk(q, ck, cv, tables, q_pos, *, kv_heads, block_size, scale,
                  interpret, sliding=0):
    """A prefill chunk's call, a function of its own (a program lowers
    it once): ONE request's queries q [1, T, H, Dh] over the dense pool
    rows of `kv_heads` heads its table [1, W] addresses, causal from the
    table's first row under `row <= q_pos[t]` — or, `sliding` > 0, the
    layer's window: the table is the run its rows lie in by position
    modulo its length, and a query sees its last `sliding` positions —
    -> [1, T, H * Dh] float32.
    The grid runs over tiles of query positions; the MXU takes a K/V
    head's `G * tq` query rows against that head's 128-lane slices of
    the tile, not the decode walk's block-diagonal queries, which would
    multiply every head's lanes for each."""
    B, T, H, Dh = q.shape
    W, bs, KV = tables.shape[1], block_size, kv_heads
    G = H // KV
    tiles = prefill_tiles(T, H, KV, Dh, bs, W, ck.dtype.itemsize)
    if B != 1 or G * KV != H or Dh % 128 or ck.shape[1] != KV * Dh \
            or tiles is None:
        raise ValueError(
            f"paged attention kernel: a prefill chunk's walk takes one "
            f"request's queries ({B} given) on whole groups of K/V heads "
            f"({H} on {KV}) of whole 128-lane tiles ({Dh} values, rows of "
            f"{ck.shape[1]} lanes), in tiles of {_PREFILL_QUERIES} queries "
            f"that divide the chunk ({T}) and fit VMEM")
    tq, KB = tiles
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // tq,),
        in_specs=[
            pl.BlockSpec((1, tq, H * Dh), lambda i, t, s: (0, i, 0)),
            pl.BlockSpec((tq, 1), lambda i, t, s: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tq, H * Dh), lambda i, t, s: (0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, KB, bs, KV * Dh), ck.dtype),
            pltpu.VMEM((2, KB, bs, KV * Dh), cv.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, G * tq, Dh), q.dtype),
            pltpu.VMEM((KV, G * tq, Dh), jnp.float32),
            pltpu.VMEM((KV, G * tq, 128), jnp.float32),
            pltpu.VMEM((KV, G * tq, 128), jnp.float32),
        ],
    )
    q_pos = q_pos.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_prefill_kernel,
                          scale=Dh ** -0.5 if scale is None else scale,
                          bs=bs, W=W, KB=KB, tq=tq, KV=KV, G=G, Dh=Dh,
                          sliding=sliding),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, T, H * Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,),
            vmem_limit_bytes=_PREFILL_TILE_BYTES + _PREFILL_REST),
        interpret=interpret,
        name="paged_attention_prefill_walk",
    )(tables.astype(jnp.int32), q_pos, q.reshape(1, T, H * Dh),
      q_pos.reshape(T, 1), ck.reshape(-1, bs, KV * Dh),
      cv.reshape(-1, bs, KV * Dh))


def latent_attention_pallas(q_row, pool, tables, q_pos, *, block_size: int,
                            rank: int, scale: float):
    """Drop-in for serving/layers.py's `latent_attention_reference`
    (tolerance parity): the walk at `kv_heads` = 1 over the one array
    that is key and value -> [B, H, T, rank] float32, the accumulator's
    first `rank` lanes."""
    out = _walk(q_row, pool, None, tables, q_pos, kv_mode="dense",
                block_size=int(block_size), kv_heads=1, scale=float(scale),
                interpret=pallas_backend.interpret())
    return out[..., :rank].transpose(0, 2, 1, 3)


@functools.partial(jax.jit,
                   static_argnames=("kv_mode", "block_size", "interpret",
                                    "window", "chunk", "kv_heads", "scale",
                                    "sliding"))
def _walk(q, ck, cv, tables, q_pos, *, kv_mode, block_size, interpret,
          window=0, chunk=0, kv_heads=None, scale=None, sliding=0):
    """The call, as a function of its own: a program that makes it in
    every layer traces and lowers the kernel once and calls it.
    `window` > 0: the table is `[window blocks | summary blocks]` and
    the walk is kernels/eva.py's (dense rows, float32 out).  `kv_heads`
    fewer than q's heads: grouped rows (dense, float32 out), a tile of
    the row's heads serving `G` = H / kv_heads query heads a key.
    `cv` None: `ck` is key and value at once (a latent row), one operand.
    `scale`: the softmax's, `Dh ** -0.5` unless given.  `sliding` > 0:
    a sliding layer's window — the table is the run its rows lie in by
    position modulo its length, and the walk is that window's blocks."""
    B, T, H, Dh = q.shape
    W = tables.shape[1]
    bs = block_size
    G = H // (kv_heads or H)
    if G * (kv_heads or H) != H:
        raise ValueError(
            f"paged attention kernel: {H} query heads are not whole "
            f"groups on {kv_heads} K/V heads a row")
    HD = H // G * Dh
    C = score_rows(T, H)
    Hp = C // T

    if kv_mode == "dense":
        marker = 0
        out_dtype = jnp.float32 if window or G > 1 else ck.dtype
        operands = [ck] if cv is None else [ck, cv]
        width = ck.shape[1]  # H * Dh and the lanes that pad a pool row
    else:
        from ..runtime.comm.quant import qmax

        marker = -qmax(kv_mode) - 1
        out_dtype = jnp.float32
        (pk, sk), (pv, sv) = ck, cv

        def bits(scales):  # fp16 -> its bit pattern, a free view
            return jax.lax.bitcast_convert_type(scales, jnp.uint16)

        operands = [pk, bits(sk), pv, bits(sv)]
        width = HD  # the tile as dequantized
    # a cache row's bytes: K's arrays are half of the operands, or the
    # one array that is key and value
    row_bytes = sum(c.shape[1] * c.dtype.itemsize
                    for c in operands) // min(len(operands), 2)
    KB = tile_blocks(bs, W, row_bytes, T, H, width)
    if KB < 1:
        raise ValueError(
            f"paged attention kernel: {T} x {H} score rows of {width} "
            f"lanes, or one block of {bs} rows of {row_bytes} bytes, "
            f"do not fit VMEM")
    bufs = [pltpu.VMEM((2, KB, bs, c.shape[1]), c.dtype) for c in operands]
    # the pool by blocks: a view, `bs` whole rows being whole tiles
    operands = [c.reshape(-1, bs, c.shape[-1]) for c in operands]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, C, width), lambda b, t, s: (b, 0, 0)),
            *[pl.BlockSpec(memory_space=pl.ANY)] * len(operands),
        ],
        out_specs=pl.BlockSpec((1, C if G > 1 else T, width),
                               lambda b, t, s: (b, 0, 0)),
        scratch_shapes=[
            *bufs,
            pltpu.SemaphoreType.DMA((len(operands), 2)),
            pltpu.VMEM((C, width), jnp.float32),
            pltpu.VMEM((C, 128), jnp.float32),
            pltpu.VMEM((C, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_walk_kernel,
                          scale=Dh ** -0.5 if scale is None else scale,
                          bs=bs, W=W, KB=KB, T=T, H=H, Hp=Hp, Dh=Dh,
                          kv_mode=kv_mode, marker=marker, window=window,
                          chunk=chunk, G=G, sliding=sliding),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C if G > 1 else T, width),
                                       out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        name="eva_attention_walk" if window else "paged_attention_walk",
    )(tables.astype(jnp.int32), q_pos.astype(jnp.int32),
      _block_diagonal(q, Hp, width, G), *operands)
    if G > 1:
        keep = _own_lanes_of_two if H // G == 2 else _own_lanes
        return keep(out, T, H, G, Dh)
    return out[..., :HD].reshape(B, T, H, Dh)


def _own_lanes_of_two(out, T: int, H: int, G: int, Dh: int):
    """`_own_lanes` for rows of TWO K/V heads, under a mask and a sum over
    the heads: the chip's compiler gets the concatenation of exactly two
    such slices wrong — rows G .. 2 G come back as neither head's
    (PERF.md section 6, PR 57: `bench_artifacts/pr57/walk_probe2.py`, the
    same expression on a plain array; one, four and eight heads are
    right and keep the slices).  What fails, under `jax.jit` on a TPU
    v5e (jax 0.9.0, libtpu 0.0.34), for float32 `rows` [B, 1, 16, 512]
    with G = 8 and Dh = 256, by 3.2 where the values' deviation is 0.22:

        jnp.concatenate([rows[:, :, 0:G, 0:Dh],
                         rows[:, :, G:2 * G, Dh:2 * Dh]], axis=2)

    the CPU and the interpreter are right.  Delete this function, and
    the fork in `_walk`, when `chip_smoke.py`'s kernels phase reads
    `own_lanes_two_slices_right: true` on the chip.  It stands behind
    `_walk` so that no line above the walk's call moves: a Mosaic
    kernel's cache entry is keyed on its source locations too."""
    B, C, width = out.shape
    rows = out.reshape(B, T, C // T, width)[:, :, :H, :2 * Dh]
    own = (jnp.arange(H)[:, None] // G) == jnp.arange(2)[None, :]
    return jnp.sum(jnp.where(own[None, None, :, :, None],
                             rows.reshape(B, T, H, 2, Dh), 0.0), axis=3)
