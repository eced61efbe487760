"""Embed, block and head of a served model, assembled from the
`LayerSpec` the model hands over (`models/layer_spec.py`): which norm,
positions, attention and FFN a block is made of.  `programs.py` builds
its prefill, decode and verify programs from these and knows no model by
name.

What is built today: learned positions with paged attention (the GPT-2
block: fused QKV with bias, every cached position attended), rotary
positions with EVA attention (the EvaByte block: exact rows for the open
window, one summary row a chunk behind it), and rotary positions over
part of a head with latent attention (the DeepSeek-V2 block: one latent
row a token for all heads, expanded to keys and values where many
queries share the expansion, attended as it lies where a query stands
alone), and positions given layer by layer with grouped attention (the
Command A+ block: `kv_heads` keys and values a row for `num_heads`
queries, query head n on K/V head n // (num_heads / kv_heads); sliding
layers rotate q and k with interleaved pairing and attend a window,
full layers have no positions and attend every cached row; one norm and
x + attn(h) + ffn(h), the "parallel" residual), and no positions at all with grouped attention in some
layers and a state-space mixer in the others (the Granite 4.0-H block:
sequential, both branches scaled; `spec.layer_mixers` says which layer
has which; below), and latent attention over a learned selection
of the cached rows (the GLM-5.2 block: `spec.layer_indexers` says which
layers score and choose — they keep a second row a token, an index key
— and which take the choice of the layer before them; below), and
positions given layer by layer with grouped attention in some layers
and a gated delta-rule mixer in the others (the Qwen3-Next block:
sequential, a routed FFN in every layer; the attention layers gate their
output, norm q and k and rotate part of a head — `spec.attn_gate`,
`qk_norm`, `rotary_dim` — and the delta layers keep a matrix state a
slot as the state-space ones do; models/qwen3_next.py, imported when
such a block is first built), and no positions with layers that are
each ONE part under one norm — a state-space mixer whose B and C come in
`spec.ssm_groups` groups of heads, grouped attention, or the FFN alone —
(the Nemotron-H block: `spec.residual` "single", a layer whose
`spec.mixer_of` is "none" is its FFN and owns neither rows nor a
state), and positions given layer by layer with grouped attention in
some layers — q and k normed over the head with the spec's own kind of
gain, then rotated whole, by halves — and a gated short convolution in
the others (the LFM2-MoE block: sequential, a routed FFN without a
shared expert behind two dense layers; the convolution layers keep their
last two inputs a slot and no other state; models/lfm2_moe.py, imported
when such a block is first built).  The norm,
the FFN and the head are free of that choice.  A routed-experts FFN,
behind leading dense layers, is one function in either block and reads
the spec (models/cohere2_moe.py `routed_ffn`): the router's scoring,
whether the chosen weights are renormalised, a bias that chooses and a
factor on the weights, which experts this chip holds, what an expert is
(what the tree holds says: kernels/expert_form.py), and how the
shared experts combine (their mean in the parallel block alone, behind
a sigmoid gate in the sequential one alone).

The paged pieces mirror models/generation.py `_block_with_cache` op for
op (fp32 scores, the same einsum strings, NEG_INF masking, probs cast to
the cache dtype), so greedy serving output is bit-identical to
`generate()` when the cache lengths agree and the attention core is the
jnp oracle (every backend but the TPU) — pinned in tests/test_serving.py.
The EVA pieces' statements stand in the order the programs had them
before they moved here: the order of independent operations is part of a
program's StableHLO, which keys the compilation cache.

The pool is `[rows, pool_width(H, Dh)]` (serving/kv_cache.py) for the
first two attentions: a token's (or a chunk summary's) heads side by side
in one row, a K and a V array a layer.  Latent attention has one array a
layer, `[rows, pool_width(1, latent_width)]`, addressed like the paged
one.  A call's K/V are written as such rows by one scatter, in place,
and attention reads the pool as it lies — through the table's live
blocks on the chip at `q_len` <= 8 (kernels/paged.py: decode and verify;
kernels/eva.py: decode), by a gather of the table's rows elsewhere and
in prefill.

Grouped rows over two groups of layers (`s.ring_blocks > 0`,
serving/kv_cache.py): a full layer's arrays are addressed through the
table's first `table_width` entries as the paged ones are; a sliding
layer's, arrays of their own, through the `ring_blocks` entries behind
them, a ring: position p lies in row `p % ring` of the run.  The position
a ring row j holds follows from the newest position written: the
largest p <= newest with p % ring = j; a query at p_q attends row j iff
that position is >= 0, <= p_q and > p_q - window.  With `ring_blocks`
0 (the ring would be no shorter than the table) sliding layers lie in
the one group and mask the window on the whole table.  A decode step
walks each slot's live blocks (kernels/paged.py, a tile of the row's
`kv_heads` heads serving `num_heads / kv_heads` query heads a key) where
the registry picks the kernel: a full layer's from the table's first
entry, a sliding layer's the blocks its window lies in, modulo the run
(`grouped_info` says `window` and `ring`).  A prefill chunk of heads of
whole lane tiles walks the same runs, a tile of its query positions a
program: a sliding layer's from the block of the tile's oldest lower
bound, where the ring holds `window + q_len - 1` rows (the engine's
does).  Every call gathers its run in `jax.numpy`
(`grouped_attention_reference`) off the chip.

Layers with a state and no rows (`spec.mixer_of(layer)` one of
models/layer_spec.py `STATE_MIXERS`, the one table that says of each
kind what a slot keeps, which function mixes, its counters' family and
its step kernel: "ssm", models/granite_hybrid.py, "gdn",
models/qwen3_next.py, or "conv", models/lfm2_moe.py): such a
layer's entry of the cache is not rows
of a pool but arrays BY SLOT — a float32 state `[slots, heads,
head_dim, state]` (`[slots, value heads, key_dim, value_dim]` for the
delta rule) and the convolution's last inputs `[slots, taps - 1,
conv_width]`, or, for the gated short convolution, those inputs alone
— `spec.state_shapes` — and its block reads and writes the entries of the call's
sequences: a prefill chunk its request's one (`Addr.slot`, which rides
behind the request's table), a decode step every slot's.  The
block moves both on by the call's valid positions (`Addr.n_valid`: a
chunk's valid tokens; 1 for a running slot, 0 for any other, whose
entries a step therefore hands back as it found them) and no term
crosses slots.  A decode step also lists its running slots once
(`Addr.live`, kernels/ssm.py `live_slots`), for every such layer: where
the registry picks the `ssm_step` (or `gdn_step`) kernel the recurrence
walks that list
and a slot that is not on it has its state neither read nor written
(the convolution kind has no kernel and no list to walk: two rows a
slot).
Nothing here zeroes a state: the engine does, when a request is seated
(serving/kv_cache.py `reset_state`).

A selection that travels (`spec.layer_indexers`, serving/sparse.py,
imported when such a block is first built): `block` takes and returns
`sel`, what the nearest "full" layer before it chose for this call's
queries — a list of pool rows a slot in a decode step, a mask over the
table's positions in a prefill chunk — and `blocks` threads it through
a program's layers.  A "full" layer's cache entry is (latent rows, index
keys), a "shared" layer's the latent rows alone.

Addressing (`Addr`): a program works out once where this call's K/V land
and what attention reads, and every layer's block uses it.  Paged: flat
write rows, the block tables, query positions (negative for a slot that
is not running: it attends nothing).  EVA: the same write rows (inside
the open window's blocks; a prefill chunk, which is whole blocks, names
the blocks instead and writes them as slabs) and query positions, the
block table `[window blocks | summary blocks]`, the flat rows the
summaries of the chunks this call completes land in, and — in decode,
where a chunk completes one token at a time — the rows of the chunk's
own block, so that its summary pools what the cache holds.  `block_size` equals the
chunk, so one exact block is one chunk and `block_size` summary rows are
one summary block.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..models import cohere2_moe
from ..kernels.ssm import live_slots
from ..models.deepseek_v2 import (absorb, absorbed_attention,
                                  attend_expanded, attend_rows,
                                  latent_project, rms_norm_plain,
                                  softmax_scale)
from ..models.evabyte import (chunk_summaries, matmul32, project_qkv,
                              rms_norm, silu_gated_ffn)
from ..models.gpt import layer_norm
from ..models.layer_spec import STATE_MIXERS, LayerSpec
from ..moe.dropless import experts_touched, rows_multiplied
from .kv_cache import pool_rows

BUILT = {("learned", "paged"), ("rope", "eva"), ("rope", "latent"),
         ("per_layer", "grouped"), ("none", "grouped")}


def check_spec(spec: LayerSpec) -> LayerSpec:
    spec.validate()
    if (spec.positions, spec.attention) not in BUILT:
        raise NotImplementedError(
            f"serving has no block with {spec.positions!r} positions and "
            f"{spec.attention!r} attention yet; built: {sorted(BUILT)}")
    # grouped rows and the parallel block are built for each other; a
    # routed FFN reads the spec in either block.  Elsewhere grouped rows
    # serve the attention layers of a sequential hybrid: a model without
    # positions, or one whose state layers have none and whose attention
    # layers rotate
    parallel = spec.residual == "parallel"
    hybrid = spec.positions == "none" or (
        spec.positions == "per_layer" and not parallel and spec.has_state)
    if (spec.attention == "grouped") != (parallel or hybrid) or (
            parallel and spec.ffn != "routed_experts"):
        raise NotImplementedError(
            f"serving builds the parallel block over grouped attention "
            f"and a routed_experts FFN, and grouped attention elsewhere "
            f"only in a sequential block without positions or with layers "
            f"that keep a state; got a {spec.residual!r} "
            f"residual with {spec.attention!r} attention, "
            f"{spec.positions!r} positions, layer_mixers "
            f"{spec.layer_mixers} and a {spec.ffn!r} FFN")
    if hybrid and (parallel or spec.layer_windows
                   or spec.ffn not in ("silu_gated", "routed_experts")
                   or spec.norm not in ("rmsnorm", "rmsnorm_unit_offset")):
        raise NotImplementedError(
            f"a hybrid of state layers and grouped attention is built as "
            f"the sequential block with RMSNorm (a gain, or 1 + g), a "
            f"silu_gated or routed_experts FFN and no window; got a "
            f"{spec.residual!r} residual, a {spec.norm!r} norm, a "
            f"{spec.ffn!r} FFN and layer_windows {spec.layer_windows}")
    if spec.has_state and any(
            spec.mixer_of(i) != "attention" and spec.rotates(i)
            for i in range(len(spec.layer_mixers))):
        raise NotImplementedError(
            f"a layer that keeps a state has no positions: layer_positions "
            f"{spec.layer_positions} rotates a layer of layer_mixers "
            f"{spec.layer_mixers} that does not attend")
    if spec.residual == "single" and (
            spec.positions != "none" or "gdn" in spec.layer_mixers):
        raise NotImplementedError(
            f"one part a layer (the \"single\" residual) is built without "
            f"positions, over state-space, grouped-attention and FFN "
            f"layers; got {spec.positions!r} positions and layer_mixers "
            f"{spec.layer_mixers}")
    scaled = (spec.embed_scale, spec.residual_scale, spec.logit_divisor,
              spec.attn_scale) != (1.0, 1.0, 1.0, 0.0)
    if (spec.has_state or scaled) and not hybrid:
        raise NotImplementedError(
            f"layers with a state (a state-space or a gated delta-rule "
            f"mixer) and the stream's scalars are built in the sequential "
            f"block without positions, or with positions layer by layer; "
            f"got {spec.positions!r} positions and a {spec.residual!r} "
            f"residual with layer_mixers "
            f"{spec.layer_mixers} and scalars {spec.embed_scale}, "
            f"{spec.residual_scale}, {spec.attn_scale}, "
            f"{spec.logit_divisor}")
    if spec.shared != "sum" and parallel != (spec.shared == "average"):
        raise NotImplementedError(
            f"the parallel block's routed FFN averages or sums its shared "
            f"experts, the sequential block's sums them or weighs them by "
            f"a sigmoid gate; got shared {spec.shared!r} with a "
            f"{spec.residual!r} residual")
    if (spec.attn_gate or spec.qk_norm or spec.rotary_dim
            or spec.rope_halves) and parallel:
        raise NotImplementedError(
            f"grouped attention's output gate, q/k norm and partial or "
            f"half-split rotary are built in the sequential block; got "
            f"them ({spec.attn_gate}, {spec.qk_norm}, {spec.rotary_dim}, "
            f"{spec.rope_halves}) with a 'parallel' residual")
    if spec.layer_indexers and (spec.positions != "rope"
                                or spec.ffn != "routed_experts"):
        raise NotImplementedError(
            f"a learned selection of rows is built in the sequential "
            f"block with rotary positions, latent rows and a "
            f"routed_experts FFN; got {spec.positions!r} positions and a "
            f"{spec.ffn!r} FFN")
    return spec


class Addr(NamedTuple):
    write_idx: Optional[jax.Array]        # [B*T] flat rows of this call's K/V
    q_pos: jax.Array                      # [B, T] absolute positions
    tables: Optional[jax.Array] = None    # [B, W] block tables
    sum_idx: Optional[jax.Array] = None   # eva: [B*n] flat summary rows
    write_blk: Optional[jax.Array] = None  # eva prefill: [T/bs] block ids
    chunk_src: Optional[jax.Array] = None  # eva decode: [B, bs] flat rows
    ring_idx: Optional[jax.Array] = None   # ring: [B*T] flat rows, group
    #                                        `window`'s arrays
    ring_tables: Optional[jax.Array] = None  # ring: [B, ring blocks]
    ring_newest: Optional[jax.Array] = None  # ring: [B] newest position
    #                                          the call writes
    slot: Optional[jax.Array] = None       # state: the one sequence's slot
    #                                        (None: sequence b is slot b)
    n_valid: Optional[jax.Array] = None    # state: [B] real positions of T
    live: Optional[tuple] = None           # state, decode: (the running
    #                                        slots [B], their count)


# -- embedding --------------------------------------------------------------


def _scaled(x, by: float):
    """x times a scalar of the spec; x itself where that is 1."""
    return x if by == 1.0 else x * by


def embed_chunk(spec, params, tokens, abs_pos):
    """tokens [1, C] at positions abs_pos [C] (prefill) or [R, T] at
    [R, T] (verify) -> x.  Rows past the position table clamp."""
    if spec.positions != "learned":
        return _scaled(params["wte"][tokens].astype(jnp.float32),
                       spec.embed_scale)
    # per-row gather, NOT dynamic_slice_in_dim(wpe, pos, C): when the
    # final chunk's pad rows run past the wpe table, a dynamic slice
    # CLAMPS its start backwards and shifts the VALID rows onto wrong
    # positional embeddings (silently breaking the ==generate()
    # contract); the gather keeps every valid row exact and only pad
    # rows (overwritten before read / masked) see the clamped last entry
    wpe_rows = params["wpe"][
        jnp.clip(abs_pos, 0, params["wpe"].shape[0] - 1)]
    if abs_pos.ndim == 1:
        return params["wte"][tokens] + wpe_rows[None]
    return params["wte"][tokens] + wpe_rows


def embed_step(spec, params, tokens, positions):
    """tokens [R] at positions [R] (decode) -> x [R, 1, D]."""
    if spec.positions != "learned":
        return _scaled(params["wte"][tokens].astype(jnp.float32),
                       spec.embed_scale)[:, None, :]
    return (params["wte"][tokens] +
            params["wpe"][positions])[:, None, :]


# -- addressing -------------------------------------------------------------


def address_chunk(spec, s, table, pos, abs_pos, n_valid) -> Addr:
    """One request's prefill chunk at positions abs_pos [C] through its
    table [W]."""
    bs, W = s.block_size, s.table_width
    if spec.has_state:      # behind the table's entries: the slot
        return _address_grouped(
            s, table[None, :-1], abs_pos[None, :], abs_pos[None, -1]
        )._replace(slot=table[-1], n_valid=n_valid[None])
    if spec.attention == "grouped":
        return _address_grouped(s, table[None, :], abs_pos[None, :],
                                abs_pos[None, -1])
    if spec.attention != "eva":
        blk_i = abs_pos // bs
        # positions past the table (pad rows of the final chunk)
        # write to the trash block, never a neighbour's memory
        blk = jnp.where(blk_i < W, table[jnp.clip(blk_i, 0, W - 1)], 0)
        write_idx = blk * bs + abs_pos % bs
        return Addr(write_idx=write_idx, q_pos=abs_pos[None, :],
                    tables=table[None, :])
    # eva: the chunk lies inside one window (prefill_chunk divides it)
    # and is whole blocks (a block is a chunk of the model's), so its
    # rows are written a block at a time — slabs of whole tiles, where
    # a scatter of single bf16 rows rewrites the tile each shares with
    # its neighbour.  A block with a valid row goes where the table
    # says, its rows past n_valid with it (masked until decode rewrites
    # them); blocks wholly past n_valid and the summaries of chunks the
    # call does not complete go to the trash block
    wb = spec.window // bs
    n_c = abs_pos.shape[0] // spec.chunk
    first = jnp.arange(n_c) * spec.chunk
    write_blk = jnp.where(first < n_valid,
                          table[(pos % spec.window + first) // bs], 0)
    c = pos // spec.chunk + jnp.arange(n_c)
    done = first + spec.chunk <= n_valid
    sblk = table[jnp.clip(wb + c // bs, 0, W - 1)]
    sum_idx = jnp.where(done, sblk * bs + c % bs, c % bs)
    return Addr(write_idx=None, q_pos=abs_pos[None, :],
                tables=table[None, :], sum_idx=sum_idx, write_blk=write_blk)


def address_step(spec, s, tables, positions, active) -> Addr:
    """One token for every slot at positions [R] through tables [R, W].
    Inactive slots write to the trash block."""
    bs, W = s.block_size, s.table_width
    if spec.attention == "grouped":
        addr = _address_grouped(
            s, tables, jnp.where(active, positions, -1)[:, None], positions)
        if not spec.has_state:
            return addr
        # the live list, once for every state-space layer of the step
        return addr._replace(n_valid=active.astype(jnp.int32),
                             live=live_slots(active))
    if spec.attention != "eva":
        blk_i = positions // bs
        blk = jnp.take_along_axis(
            tables, jnp.clip(blk_i, 0, W - 1)[:, None], axis=1)[:, 0]
        write_idx = jnp.where(active, blk * bs + positions % bs, 0)
        q_pos = jnp.where(active, positions, -1)[:, None]
        return Addr(write_idx=write_idx, q_pos=q_pos, tables=tables)
    wb = spec.window // bs
    off = positions % spec.window
    blk = jnp.where(active, jnp.take_along_axis(
        tables, (off // bs)[:, None], axis=1)[:, 0], 0)
    write_idx = blk * bs + jnp.where(active, off % bs, 0)
    # the token at the last offset of a chunk completes it: its summary,
    # pooled from the chunk's own block, goes to summary row `c`
    c = positions // spec.chunk
    done = active & (positions % spec.chunk == spec.chunk - 1)
    sblk = jnp.take_along_axis(
        tables, jnp.clip(wb + c // bs, 0, W - 1)[:, None], axis=1)[:, 0]
    sum_idx = jnp.where(done, sblk * bs + c % bs, 0)
    chunk_src = blk[:, None] * bs + jnp.arange(bs)[None, :]
    q_pos = jnp.where(active, positions, -1)[:, None]
    return Addr(write_idx=write_idx, q_pos=q_pos, tables=tables,
                sum_idx=sum_idx, chunk_src=chunk_src)


def _address_grouped(s, tables, pos, newest) -> Addr:
    """Grouped rows: tables [B, table_width (+ ring_blocks)], the
    positions [B, T] this call writes and attends from (negative, or past
    the table: the trash block, and nothing attended) and the newest
    position it writes [B], which says what a ring's rows hold."""
    bs, W = s.block_size, s.table_width
    B, T = pos.shape

    def rows(run, blk_i, ok):
        blk = jnp.take_along_axis(
            run, jnp.clip(blk_i, 0, run.shape[1] - 1), axis=1)
        return jnp.where(ok, blk * bs + pos % bs, 0).reshape(B * T)

    live = pos >= 0
    write_idx = rows(tables[:, :W], pos // bs, live & (pos // bs < W))
    if not s.ring_blocks:
        return Addr(write_idx=write_idx, q_pos=pos, tables=tables)
    ring = s.ring_blocks * bs
    return Addr(write_idx=write_idx, q_pos=pos, tables=tables[:, :W],
                ring_idx=rows(tables[:, W:], pos % ring // bs, live),
                ring_tables=tables[:, W:], ring_newest=newest)


def address_grid(spec, s, tables, abs_pos, active, n_draft) -> Addr:
    """T candidate tokens for every slot at abs_pos [R, T] (verify);
    rows past a slot's `n_draft` candidates write to the trash block."""
    if spec.has_state:
        raise NotImplementedError(
            "the verify program over layers with a state is not built: a "
            "rejected draft would have moved the state on, and nothing "
            "keeps the state it would have to be rewound to")
    if spec.attention != "paged":
        raise NotImplementedError(
            "the verify program over summarised windows is not built: a "
            "rejected draft may have completed a chunk, whose summary row "
            "would have to be rewound with it")
    bs, W = s.block_size, s.table_width
    R, T = abs_pos.shape
    blk_i = abs_pos // bs
    valid = (active[:, None] &
             (jnp.arange(T)[None, :] <= n_draft[:, None]) &
             (blk_i < W))
    blk = jnp.take_along_axis(tables,
                              jnp.clip(blk_i, 0, W - 1), axis=1)
    # rows past a slot's drafts (and inactive slots) write to
    # the trash block, the decode convention
    write_idx = jnp.where(valid, blk * bs + abs_pos % bs,
                          0).reshape(R * T)
    q_pos = jnp.where(active[:, None], abs_pos, -1)
    return Addr(write_idx=write_idx, q_pos=q_pos, tables=tables)


# -- the block --------------------------------------------------------------


def _kv_write(c, idx, val, kv_mode):
    """Scatter `val` [N, H, Dh] into cache entry `c` at flat rows
    `idx`, as whole pool rows.  Dense: a plain row scatter at the
    cache's own dtype.  Quantized: the rows are quantized through the
    PR-7 row kernels and BOTH the payload and the per-(row, head) scales
    scatter at the same indices — the write never touches another row's
    scale."""
    if kv_mode == "dense":
        return c.at[idx].set(pool_rows(val.astype(c.dtype), c.shape[1]))
    from ..runtime.comm.quant import quantize_rows

    payload, scales = c
    codes, s = quantize_rows(val.astype(jnp.float32), kv_mode)
    return (payload.at[idx].set(pool_rows(codes, payload.shape[1])),
            scales.at[idx].set(s))


def _block_write(c, blk, val, bs: int):
    """Write `val` [n * bs, H, Dh] into the dense pool `c` as the `n`
    whole blocks `blk`."""
    width = c.shape[1]
    slabs = pool_rows(val.astype(c.dtype), width).reshape(-1, bs, width)
    return c.reshape(-1, bs, width).at[blk].set(slabs).reshape(c.shape)


def paged_info(cfg, s, q_len: int, cache_dtype) -> dict:
    """What the kernel registry may look at to choose the attention
    core of a paged program of `s` with `q_len` queries a slot."""
    return {"block_size": s.block_size, "table_width": s.table_width,
            "q_len": q_len, "num_heads": cfg.num_heads,
            "head_dim": cfg.head_dim, "kv_mode": s.kv_dtype,
            "kv_itemsize": jnp.dtype(cache_dtype).itemsize}


def _paged_attend(cfg, p, h, ck, cv, addr, s):
    """Fused QKV with bias, K/V written through the table, causal
    softmax over every cached row, output projection.  The math of
    generation._block_with_cache; only the cache addressing differs
    (scatter and table walk instead of dynamic_update_slice on a
    contiguous cache).  `s.kv_dtype` picks the storage codec: "dense"
    stores rows at the cache arrays' dtype, "int8"/"int4" stores
    (payload, scales) pairs dequantized at the read — the surrounding
    math is identical either way, so parity pins hold AT MATCHED
    kv_dtype."""
    B, T, D = h.shape
    H, Dh = cfg.num_heads, cfg.head_dim
    qkv = h @ p["qkv"]["w"].astype(h.dtype) + \
        p["qkv"]["b"].astype(h.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = lambda t: t.reshape(B, T, H, Dh)
    q, k, v = shape(q), shape(k), shape(v)
    kv_mode = s.kv_dtype
    ck = _kv_write(ck, addr.write_idx, k.reshape(B * T, H, Dh), kv_mode)
    cv = _kv_write(cv, addr.write_idx, v.reshape(B * T, H, Dh), kv_mode)
    # attention core through the kernel registry: the jnp oracle
    # (kernels/paged.py paged_attention_reference) is this block's
    # pre-registry gather/einsum/softmax chain — wherever the oracle is
    # chosen, serving output is bit-identical to generate(); the Pallas
    # kernel walks each slot's live blocks in the pool, an online
    # softmax over tiles of them
    from ..kernels import registry

    cache_dtype = ck.dtype if kv_mode == "dense" else ck[0].dtype
    attn = registry.dispatch(
        "paged_attention", q, ck, cv, addr.tables, addr.q_pos,
        info=paged_info(cfg, s, T, cache_dtype),
        kv_mode=kv_mode, block_size=s.block_size)
    attn = attn.reshape(B, T, D)
    attn = attn @ p["proj"]["w"].astype(h.dtype) + \
        p["proj"]["b"].astype(h.dtype)
    return attn, ck, cv


def eva_info(spec, cfg, s, q_len: int, cache_dtype) -> dict:
    """`paged_info` and the two sizes that say which table entries a
    position makes live."""
    return dict(paged_info(cfg, s, q_len, cache_dtype),
                window=spec.window, chunk=spec.chunk)


def _eva_attend(spec, cfg, p, h, ck, cv, addr, s):
    """q, k, v with rotary positions at the cache's dtype; this call's
    exact rows written into the open window's blocks; the summaries of
    the chunks this call completes written to their summary rows; then
    one softmax over the window's rows up to the query and the summary
    rows of closed windows (kernels/eva.py: the walk of a slot's live
    blocks on the chip at `q_len` <= 8, the gather of its whole table
    elsewhere and in prefill).  -> float32."""
    B, T, D = h.shape
    H, Dh = cfg.num_heads, cfg.head_dim
    q, k, v = project_qkv(p, h, addr.q_pos, spec.rope_theta, H, ck.dtype)
    if addr.chunk_src is None:   # prefill: whole chunks, as just stored
        ck = _block_write(ck, addr.write_blk, k.reshape(T, H, Dh),
                          s.block_size)
        cv = _block_write(cv, addr.write_blk, v.reshape(T, H, Dh),
                          s.block_size)
        chunks = lambda t: t.reshape(B, T // spec.chunk, spec.chunk, H, Dh)
        ks, vs = chunk_summaries(chunks(k), chunks(v), p["mu"], p["phi"])
    else:                        # decode: the chunk's block, as stored
        ck = _kv_write(ck, addr.write_idx, k.reshape(B * T, H, Dh), "dense")
        cv = _kv_write(cv, addr.write_idx, v.reshape(B * T, H, Dh), "dense")
        stored = lambda c: c[addr.chunk_src][..., :H * Dh].reshape(
            B, -1, H, Dh)
        ks, vs = chunk_summaries(stored(ck), stored(cv), p["mu"], p["phi"])
    ck = _kv_write(ck, addr.sum_idx, ks.reshape(-1, H, Dh), "dense")
    cv = _kv_write(cv, addr.sum_idx, vs.reshape(-1, H, Dh), "dense")
    from ..kernels import registry

    attn = registry.dispatch(
        "eva_attention", q, ck, cv, addr.tables, addr.q_pos,
        info=eva_info(spec, cfg, s, T, ck.dtype),
        window=spec.window, chunk=spec.chunk, block_size=s.block_size)
    return matmul32(attn.reshape(B, T, D), p["o"]), ck, cv


def latent_info(cfg, s, q_len: int, cache_dtype, width: int) -> dict:
    """`paged_info` over latent rows: one array a layer that is key and
    value at once, one "K/V head" of `width` values (the latent c beside
    the rotated key) that every query head reads."""
    return dict(paged_info(cfg, s, q_len, cache_dtype),
                kv_heads=1, head_dim=width)


def _table_rows(pool, tables, block_size: int, width: int):
    """Every entry of `tables` [B, W], whatever a slot holds, as slabs of
    `block_size` rows, not row by row: a gather of 1,280-byte rows ran at
    an eighth of the chip's bandwidth (PERF.md, PR 37).  -> [B, L, width]"""
    lanes = pool.shape[1]
    return pool.reshape(-1, block_size, lanes)[tables].reshape(
        tables.shape[0], -1, lanes)[..., :width]


def latent_attention_reference(q_row, pool, tables, q_pos, *,
                               block_size: int, rank: int, scale: float):
    """The absorbed core of `_latent_attend`: q_row [B, T, H, width] over
    the latent rows that `tables` [B, W] address in the pool, gathered a
    block at a time — every entry, whatever a slot holds — causal from
    the table's first row for queries at q_pos [B, T].
    -> [B, H, T, rank] float32."""
    held = _table_rows(pool, tables, block_size, q_row.shape[-1])
    mask = q_pos[:, :, None] >= jnp.arange(held.shape[1])[None, None, :]
    return attend_rows(q_row, held, mask, rank, scale)


def _latent_attend(cfg, p, h, pool, addr, s):
    """Queries and this call's latent rows ([c after its norm | rotated
    key], one a token for all heads) at the cache's dtype; the rows
    written through the table; causal softmax over every cached row of
    the slot — the table's rows gathered and expanded through W_kv_b
    where the call's queries share that product (a prefill chunk); W_kv_b
    absorbed into query and output where they do not (decode), over the
    rows as they lie (through the kernel registry: the walk of each
    slot's live blocks on the chip at `q_len` <= 8, kernels/paged.py;
    the gather of every table entry elsewhere); output projection.
    -> float32."""
    B, T, _ = h.shape
    q_nope, q_rope, rows = latent_project(cfg, p, h, addr.q_pos, pool.dtype)
    pool = _kv_write(pool, addr.write_idx, rows.reshape(B * T, 1, -1),
                     "dense")
    width, bs = rows.shape[-1], s.block_size
    L = addr.tables.shape[1] * bs
    if absorb(cfg, T, L):
        from ..kernels import registry

        out = absorbed_attention(
            cfg, p["kv_b"], q_nope, q_rope, pool.dtype,
            lambda q_row: registry.dispatch(
                "latent_attention", q_row, pool, addr.tables, addr.q_pos,
                info=latent_info(cfg, s, T, pool.dtype, width),
                block_size=bs, rank=cfg.kv_lora_rank,
                scale=softmax_scale(cfg.head_dim, cfg.yarn)))
    else:
        held = _table_rows(pool, addr.tables, bs, width)
        mask = addr.q_pos[:, :, None] >= jnp.arange(L)[None, None, :]
        out = attend_expanded(cfg, p["kv_b"], q_nope, q_rope, held, mask)
    return matmul32(out, p["o"]), pool


def grouped_info(spec, cfg, s, q_len: int, cache_dtype, window: int = 0,
                 ringed: bool = False, batch: int = 1) -> dict:
    """`paged_info` over rows of `kv_heads` heads, what the layer's
    rows are beside one causal run of the table — a `window` (0: none),
    a `ring`, whose entries are then the table the call hands over — and
    how many sequences the call's queries belong to."""
    info = dict(paged_info(cfg, s, q_len, cache_dtype),
                kv_heads=spec.kv_heads, window=window, ring=ringed,
                batch=batch)
    if ringed:
        info["table_width"] = s.ring_blocks
    return info


def grouped_attention_reference(q, ck, cv, tables, q_pos, *, kv_heads: int,
                                block_size: int, scale=None,
                                window: int = 0, newest=None):
    """The `_grouped_attend` attention core: q [B, T, H, Dh] over the
    rows of `kv_heads` heads that `tables` [B, W] address in the pool,
    gathered a block at a time — every entry, whatever a slot holds —
    under what a query at q_pos [B, T] may see: causal, inside `window`
    where there is one; `newest` [B]: the rows are a ring, and row j
    holds the largest position <= newest that is j modulo the ring.
    -> [B, T, H * Dh] float32."""
    B, Dh = q.shape[0], q.shape[3]
    held = lambda c: _table_rows(c, tables, block_size, kv_heads * Dh
                                 ).reshape(B, -1, kv_heads, Dh)
    keys, vals = held(ck), held(cv)
    L = keys.shape[1]
    at = jnp.arange(L)[None, :]
    if newest is not None:
        newest = newest[:, None]
        at = newest - (newest - at) % L                      # [B, L]
    return cohere2_moe.attend_grouped(
        q, keys, vals, _visible(at, q_pos, window), scale=scale)


def _grouped_attend(spec, cfg, p, h, ck, cv, addr, s, layer: int):
    """q of `num_heads` heads and this call's rows of `kv_heads` keys and
    values at the cache's dtype, rotated where the layer rotates; the
    rows written — into the request's ring where the layer has a window
    and the cache two groups, through the table else; softmax over the
    rows the layer lets a query see — q and k normed, part of the head
    rotated and the attended values gated where the spec says so
    (models/qwen3_next.py `project_gated`) — (through the kernel registry: on
    the chip, the walk of each slot's live blocks in a decode or verify
    step — the whole table's, or under a window the window's, modulo the
    ring — and of the one request's, by the same two rules, in a prefill
    chunk of heads of whole 128-lane tiles, kernels/paged.py; the gather
    of every table entry elsewhere, over a ring too short for the walk's
    mask and in a chunk of narrower heads); output projection.
    -> float32."""
    B, T, _ = h.shape
    KV, Dh = spec.kv_heads, cfg.head_dim
    window = spec.window_of(layer)
    gate = None
    if spec.attn_gate or spec.qk_norm or spec.rotary_dim or spec.rope_halves:
        from ..models.qwen3_next import project_gated

        q, k, v, gate = project_gated(
            cfg, spec, p, h, addr.q_pos, spec.rotates(layer), ck.dtype)
    else:
        q, k, v = cohere2_moe.project_grouped(
            cfg, p, h, addr.q_pos, spec.rotates(layer), ck.dtype)
    ringed = window > 0 and addr.ring_idx is not None
    idx, tables = (addr.ring_idx, addr.ring_tables) if ringed \
        else (addr.write_idx, addr.tables)
    ck = _kv_write(ck, idx, k.reshape(B * T, KV, Dh), "dense")
    cv = _kv_write(cv, idx, v.reshape(B * T, KV, Dh), "dense")
    from ..kernels import registry

    out = registry.dispatch(
        "grouped_attention", q, ck, cv, tables, addr.q_pos,
        info=grouped_info(spec, cfg, s, T, ck.dtype, window, ringed, B),
        kv_heads=KV, block_size=s.block_size,
        scale=spec.attn_scale or None, window=window,
        newest=addr.ring_newest if ringed else None)
    if gate is not None:
        out = out * jax.nn.sigmoid(gate)
    return matmul32(out, p["o"]), ck, cv


def _state_mix(mix, spec, p, h, kept, addr):
    """`mix`, a mixer with a state (`STATE_MIXERS`), over the call's
    sequences: their entries of the arrays `kept` that the layer keeps
    by slot (a state and the convolution's inputs, or those inputs
    alone) — slot `addr.slot`'s for the one sequence of a prefill chunk,
    every slot's in a decode step — moved on by `addr.n_valid`
    positions each and written back in place.  -> (float32, *kept)."""
    if addr.slot is None:
        return mix(spec, p, h, *kept, addr.n_valid, addr.live)
    take = lambda a: jax.lax.dynamic_slice_in_dim(a, addr.slot, 1)
    put = lambda a, new: jax.lax.dynamic_update_slice_in_dim(
        a, new, addr.slot, 0)
    out, *new = mix(spec, p, h, *map(take, kept), addr.n_valid)
    return (out, *map(put, kept, new))


def _visible(at, q_pos, window: int):
    """Which rows a query sees: rows holding positions `at` [B | 1, L]
    (negative: nothing yet) for queries at q_pos [B, T] -> [B, T, L]:
    causal, and inside the window where the layer has one."""
    at, q_pos = at[:, None, :], q_pos[:, :, None]
    mask = (at <= q_pos) & (at >= 0)
    if window:
        mask &= at > q_pos - window
    return mask


def _parallel_block(spec, cfg, p, x, kv, addr, s, layer: int, count: str):
    """One norm, attention and the routed FFN the spec describes on the
    same h, both added to x: -> (x, kv, the layer's `count`)."""
    with jax.named_scope("attn"):    # the shared norm goes with it
        h = _norm(spec, x, p["ln1"])
        with jax.named_scope("swa_attend" if spec.window_of(layer)
                             else "full_attend"):
            attn, *kv = _grouped_attend(spec, cfg, p["attn"], h, *kv, addr,
                                        s, layer)
    with jax.named_scope("ffn"):
        live = addr.q_pos.reshape(-1) >= 0
        if count == "touched":   # as a decode step's program has had it
            y, n = cohere2_moe.expert_ffn(spec, cfg, p["mlp"], h, live=live)
        else:
            y, idx, _, held = cohere2_moe.routed_ffn(
                spec, cfg, p["mlp"], h.reshape(-1, h.shape[-1]), live)
            y = y.reshape(h.shape)
            n = rows_multiplied(idx, p["mlp"]["experts"], cfg.num_experts,
                                held, live)
    # each branch's residual add under its own stage, where the program
    # has had them: behind both branches
    with jax.named_scope("attn"):
        x = x + attn
    with jax.named_scope("ffn"):
        return x + y, tuple(kv), n


def _norm(spec, x, p):
    if spec.norm == "layernorm":
        return layer_norm(x, p, spec.eps)
    if spec.norm == "layernorm_gain":
        return cohere2_moe.layer_norm_gain(x, p, spec.eps)
    if spec.norm == "rmsnorm":
        return rms_norm_plain(x, p, spec.eps)
    return rms_norm(x, p, spec.eps)


def _ffn(spec, p, h):
    if spec.ffn != "gelu_mlp":   # a routing model's leading dense layers too
        return silu_gated_ffn(p, h)
    h = h @ p["fc1"]["w"].astype(h.dtype) + \
        p["fc1"]["b"].astype(h.dtype)
    h = jax.nn.gelu(h, approximate=True)
    return h @ p["fc2"]["w"].astype(h.dtype) + \
        p["fc2"]["b"].astype(h.dtype)


def blocks(spec, cfg, params, x, caches, addr, s, count: str = "touched"):
    """Every layer's `block` in turn over x -> (x, the new cache
    entries, the `count` of the layers that have one), a selection that
    a layer makes handed to the layers behind it."""
    new_caches, touched, sel = [], [], None
    for i, (bp, kv) in enumerate(zip(params["blocks"], caches)):
        x, kv, n, sel = block(spec, cfg, bp, x, kv, addr, s, i, sel, count)
        new_caches.append(kv)
        if n is not None:
            touched.append(n)
    return x, new_caches, touched


def block(spec, cfg, p, x, kv, addr, s, layer: int = 0, sel=None,
          count: str = "touched"):
    """Pre-norm decoder block number `layer` over x [B, T, D] through
    its entry `kv` of the cache of a program of schedule `s` -> (x, kv,
    touched, sel): `touched` is None, or, behind a routed FFN, what the
    program asked the layer to `count`, int32: "touched", how many of
    its experts the call's live tokens (`addr.q_pos` >= 0) chose (behind
    a share of the experts: of those held; a decode step's), or "rows",
    how many assignment rows its routed product multiplied with an
    expert's matrices (`moe/dropless.py::rows_multiplied`; a prefill
    chunk's); `sel` is the selection of rows the layer attended where
    the spec has layers that choose (None elsewhere), for the layers
    behind it.  Under the "single" residual the layer is ONE part under
    its one norm (models/nemotron_h.py): its mixer, under the mixer's
    stage, or — `spec.mixer_of(layer)` "none", a cache entry of nothing
    — its FFN under `ffn`."""
    if spec.residual == "parallel":
        return _parallel_block(spec, cfg, p, x, kv, addr, s, layer,
                               count) + (None,)
    mixer, single = spec.mixer_of(layer), spec.residual == "single"
    if mixer != "none":
        with jax.named_scope("attn" if mixer == "attention" else "state"):
            h = _norm(spec, x, p["ln1"])
            attn, kv, sel = _mix(spec, cfg, p, h, kv, addr, s, layer, sel,
                                 mixer)
            x = x + _scaled(attn, spec.residual_scale)
        if single:               # the layer is its mixer and nothing else
            return x, tuple(kv), None, sel
    with jax.named_scope("ffn"):  # (a single layer has the one norm)
        h = _norm(spec, x, p["ln1" if single else "ln2"])
        if spec.ffn != "routed_experts" or layer < spec.dense_layers:
            return (x + _scaled(_ffn(spec, p["mlp"], h),
                                spec.residual_scale),
                    tuple(kv), None, sel)
        live = addr.q_pos.reshape(-1) >= 0
        y, idx, held_of, held = cohere2_moe.routed_ffn(
            spec, cfg, p["mlp"], h.reshape(-1, h.shape[-1]), live)
        # reshaped before the count, as this block's programs have it
        # (the order of independent operations is part of their StableHLO)
        y = y.reshape(h.shape)
        touched = experts_touched(idx, live, held_of, held) \
            if count == "touched" else rows_multiplied(
                idx, p["mlp"]["experts"], cfg.num_experts, held, live)
        return x + y, tuple(kv), touched, sel


def _mix(spec, cfg, p, h, kv, addr, s, layer: int, sel, mixer: str):
    """Layer `layer`'s mixer — attention of the spec's kind, or the
    kind of `STATE_MIXERS` that `mixer` names — over the normed h
    through its cache entry -> (mixed, kv, sel)."""
    if spec.layer_indexers:
        from .sparse import sparse_latent_attend

        return sparse_latent_attend(
            spec, cfg, p["attn"], h, kv, addr, s, layer, sel, _kv_write)
    if mixer in STATE_MIXERS:
        with jax.named_scope(f"{mixer}.step" if h.shape[1] == 1
                             else f"{mixer}.scan"):
            attn, *kv = _state_mix(STATE_MIXERS[mixer].mix_fn(), spec,
                                   p[mixer], h, kv, addr)
    elif spec.attention == "grouped":
        with jax.named_scope("gated_attend" if spec.attn_gate
                             else "full_attend"):
            attn, *kv = _grouped_attend(spec, cfg, p["attn"], h, *kv,
                                        addr, s, layer)
    elif spec.attention == "paged":
        with jax.named_scope("paged_attend"):
            attn, *kv = _paged_attend(cfg, p["attn"], h, *kv, addr, s)
    elif spec.attention == "eva":
        with jax.named_scope("eva_attend"):
            attn, *kv = _eva_attend(spec, cfg, p["attn"], h, *kv, addr, s)
    else:
        with jax.named_scope("mla_attend"):
            attn, *kv = _latent_attend(cfg, p["attn"], h, *kv, addr, s)
    return attn, kv, sel


# -- head -------------------------------------------------------------------


def final_norm(spec, params, x):
    return _norm(spec, x, params["ln_f"])


def logits(spec, params, x_rows):
    """[B, D] normed hidden rows -> fp32 logits [B, V] (generation.py's
    head), or, where the spec says `fp32_logits`, the float32 product
    at full precision."""
    w = params["wte"].T if spec.head == "tied" else params["lm_head"]
    if spec.fp32_logits:
        out = jnp.dot(x_rows, w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    elif x_rows.dtype != w.dtype:  # float32 rows of a bf16 model: multiply
        out = matmul32(x_rows, w)  # at the head's dtype, do not copy it
    else:
        out = (x_rows @ w.astype(x_rows.dtype)).astype(jnp.float32)
    return out if spec.logit_divisor == 1.0 else out / spec.logit_divisor


def sampled(spec, row_logits):
    """The logits the next token is drawn from: a multi-head output
    decodes from its first head."""
    if not spec.sample_vocab:
        return row_logits
    return row_logits[..., :spec.sample_vocab]
