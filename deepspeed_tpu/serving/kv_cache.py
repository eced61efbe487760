"""The serving cache: a plan of layer groups, a pool of fixed-size blocks
behind one refcounted free list, per-request block tables, and a
block-level prefix cache.

The serving problem the static cache in models/generation.py cannot
solve: a decode batch whose membership changes every step.  Paging
(vLLM's PagedAttention recipe, PAPERS.md) breaks the cache into
fixed-size blocks owned by a host-side free list; a request holds the
blocks its length needs, a finished request returns them the same step,
and the programs address rows through a per-request block table — so
fragmentation is bounded at one partly filled block a request and
admission is a free-list check, not a compaction.

The plan (`cache_plan(spec, model config, ServeConfig) -> CachePlan`) is
the one place that knows what each layer of a served model keeps.  The
engine, the schedule and the cache read it; nothing else decides it.

* A `LayerGroup` is some of the model's layers that keep the same
  thing.  `keeps` says what an entry of `caches` is for them:
  "blocks" — arrays of `num_blocks * block_size` rows, one an entry of
  `arrays` (a (K, V) pair; one latent row for all heads; a latent row
  and an index key), every group by block under the SAME block ids;
  "ring" — a (K, V) pair in a pool of the group's own,
  `slots * ring_blocks + 1` blocks with a free list and a trash block of
  its own, so it never runs dry and admission need not ask it;
  "slots" — arrays by slot, `[slots, *shape]` (a state mixer's float32
  state and its convolution's last inputs), no blocks and no table
  entries: `reset_state(slot)` zeroes a slot's when a request is seated;
  "nothing" — `()`, a layer that is its FFN alone.
* `run` says which run of a request's table names the group's blocks
  and how a position maps to a row of it.  "life": entries
  `[0, table_width)`, the row of position p is row p, handed out whole
  at admission (`alloc`).  "window": the table's first `window_blocks`
  entries are the open window's exact blocks (position p at row
  `p % window`), the rest summary blocks, one row per `block_size`
  tokens; nothing is handed out at admission — `alloc` books the bounded
  footprint (`reserve`), `extend` takes blocks as positions are about to
  be written, `close_window` gives a full window's exact blocks back
  mid-request while its summary rows stay; booked-but-not-held blocks
  are `promised` and not free for admission, so `extend` never fails.
  "ring": `ring_blocks` entries BEHIND the table's `table_width`, the
  row of position p is row `p % ring` of the run (`window +
  prefill_chunk` rows in whole blocks: a chunk's rows are written before
  its oldest query has attended the window behind it); `extend` takes
  them from the group's own list as positions are first written.  Which
  position a row holds is the programs' arithmetic (serving/layers.py).
* Admission books `blocks_needed(n)` for a request of n tokens: every
  token's block on a "life" run, at most a window of exact blocks plus
  the blocks of one summary row per `block_size` tokens on a "window"
  run, nothing for a ring or for slots.
* Where the prefix cache, session pins or a mesh are not offered for a
  group, the group carries the sentence that says why
  (`no_prefix_cache`, `no_sessions`, `no_mesh`: the engine raises the
  first it finds), and `refusal` is what this module answers when asked
  to lay such a group out quantized, sharded or behind a prefix cache.
* `KINDS` says, of a group's kind, which kernel-registry op decides
  whether its attention (or its step) walks what is live or reads
  everything, and how a decode step's and a prefill chunk's rows are
  counted for it; the engine's step loop names no kind.

Prefix cache: a FULL, immutable block's content is named by a token-id
chain hash `h_i = H(h_{i-1}, tokens_in_block_i)` salted with the model
fingerprint and the storage mode.  Blocks are refcounted: N requests
alias ONE physical block by putting the same id in their tables (the
read path cannot tell, which keeps greedy serving bitwise-identical to
`generate()` with the cache on).  A finished holder's registered blocks
park in an LRU of refcount-0 blocks: still matchable, evicted only when
the free list runs dry — never a live holder.  The partly filled tail
block is always private, and the one write that can land in a shared
block — the recompute of the final prompt token when the whole prompt is
cached — goes copy-on-write (`kv.cow_copies`).  Only prefill-written
rows are ever registered.  Session pins ride the same refcounts:
`pin(owner, rid)` takes one extra reference on a finished request's
blocks so a follow-up turn adopts them wholesale (`alloc_from_pin`).
Both are offered over exact rows on a "life" run only.

Device layout: an array by block is `[rows, pool_width(H, w)]`: a
token's H heads side by side in one row, rounded up to the 128 lanes the
chip tiles a row in anyway.  A block is `block_size` consecutive rows —
one contiguous slab — and the programs use the pool as it lies: a row
scatter at `table[pos // bs] * bs + pos % bs`, a read that gathers the
table's blocks (the oracle) or copies each live block (kernels/paged.py).
Kept as `[rows, H, Dh]` the chip transposes all of it for every scatter
and gather (PERF.md PR 30).  On a mesh a (K, V) pair's lanes are sharded
over the `model` axis like the weights.  Quantized storage
(`dtype="int8" | "int4"`, a (K, V) pair only): each array becomes a
(payload, scales) pair — codes `[rows, pool_width(H, Dh | Dh/2)]` and
one fp16 scale per (row, head) (runtime/comm/quant.py), a granularity
finer than a block, so a decode write touches its own rows' scales only.

Block 0 is the reserved TRASH block: never handed out, tables are padded
with it and inactive slots write to it, so the programs need no branch
for "this entry is not real".

Counters (monitor/counters.py): `kv.blocks_in_use` (sampled each step),
`kv.evictions` (blocks reclaimed from requests that did not finish),
`kv.prefix_hits`, `kv.prefix_hit_tokens`, `kv.cow_copies`,
`kv.session_pins`, `kv.prefix_evictions`; a "window" run adds
`kv.window_closes` and `kv.summary_rows`, a ring `kv.ring_wraps`, slots
`<family>.state_resets`; the kinds' own are listed in serving/engine.py.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.eva import live_blocks
from ..models.layer_spec import STATE_MIXERS
from ..monitor.counters import COUNTERS

TRASH_BLOCK = 0

# quantized storage modes (PR-7 kernels, runtime/comm/quant.py) — the
# cache stores (payload, scales) per K/V entry instead of a dense array
KV_QUANT_WIRES = ("int8", "int4")

# accepted string spellings for dense kv dtypes
_KV_DTYPE_ALIASES = {
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "fp16": jnp.float16, "float16": jnp.float16,
    "fp32": jnp.float32, "float32": jnp.float32,
}


def resolve_kv_dtype(dtype):
    """Normalize a kv_dtype spec -> ("dense", jnp dtype) or
    ("int8" | "int4", None).  Accepts quant-wire strings, dense dtype
    name strings ("bf16", "float32", ...), or dtype-likes."""
    if isinstance(dtype, str):
        name = dtype.lower()
        if name in KV_QUANT_WIRES:
            return name, None
        if name in _KV_DTYPE_ALIASES:
            return "dense", _KV_DTYPE_ALIASES[name]
        raise ValueError(
            f"kv_dtype {dtype!r} not understood; use one of "
            f"{sorted(_KV_DTYPE_ALIASES)} or {KV_QUANT_WIRES}")
    return "dense", dtype


LANES = 128


def pool_width(num_heads: int, width: int) -> int:
    """Lanes of one pool row: `num_heads * width` values side by side,
    rounded up to the chip's 128-lane tile — what the row occupies on
    the device whatever its logical width, and what lets the paged
    kernel copy a block as whole tiles."""
    return -(-num_heads * width // LANES) * LANES


def pool_rows(val, width: Optional[int] = None):
    """[N, H, w] -> [N, width]: a token's heads side by side, then the
    lanes that pad a pool row (`pool_width(H, w)` unless given)."""
    n, heads, w = val.shape
    width = pool_width(heads, w) if width is None else width
    return jnp.pad(val.reshape(n, heads * w),
                   ((0, 0), (0, width - heads * w)))


def rows_for_tables(tables, block_size: int):
    """Block tables [R, W] -> flat cache row indices [R, W * block_size]
    (row-major walk of each slot's blocks): the addressing the paged
    oracle gathers through, and the walk the paged kernel makes block
    by block."""
    R, W = tables.shape
    return (tables[:, :, None] * block_size +
            jnp.arange(block_size)[None, None, :]).reshape(R, -1)


def kv_block_bytes(num_layers: int, num_heads: int, head_dim: int,
                   block_size: int, kv_dtype) -> int:
    """Device bytes ONE block costs across all layers (K and V) — the
    equal-pool-bytes sizing rule serve_bench's resident-sessions lanes
    ride: a row of `pool_width` values; int8 stores head_dim payload
    bytes + 2 scale bytes per (row, head), int4 halves the payload."""
    mode, dense = resolve_kv_dtype(kv_dtype)
    if mode == "dense":
        per_row = pool_width(num_heads, head_dim) * \
            jnp.dtype(dense).itemsize
    elif mode == "int8":
        per_row = pool_width(num_heads, head_dim) + 2 * num_heads
    else:  # int4: two codes per byte + the fp16 scale
        per_row = pool_width(num_heads, head_dim // 2) + 2 * num_heads
    return 2 * num_layers * block_size * per_row


# -- the plan ---------------------------------------------------------------


class LayerGroup(NamedTuple):
    """Some of a served model's layers that keep the same thing in the
    cache (the module's docstring says what each field's values mean)."""

    kind: str                  # a key of KINDS
    layers: tuple
    keeps: str = "blocks"      # "blocks" | "ring" | "slots" | "nothing"
    arrays: tuple = ()         # blocks, ring: ((name, heads, width), ...),
    #                            one pool array each; slots: ((shape a
    #                            slot, dtype or None: the cache's), ...)
    run: str = "life"          # "life" | "window" | "ring" | "" (no blocks)
    window: int = 0            # tokens: the open window of a "window" run;
    #                            what a sliding layer's query attends
    topk: int = 0              # rows a learned selection attends at most
    no_prefix_cache: str = ""  # why the engine offers none (else "")
    no_sessions: str = ""
    no_mesh: str = ""          # "{n}": the mesh's devices
    refuses: tuple = ()        # of "prefix_cache", "quantized", "mesh":
    refusal: str = ""          # what the cache answers when asked for one

    def books(self, n_tokens: int, block_size: int) -> int:
        """The most blocks of the shared pool a request of `n_tokens`
        holds at once in this group."""
        exact = -(-int(n_tokens) // block_size)
        if self.run == "life":
            return exact
        if self.run != "window":
            return 0
        summary_rows = int(n_tokens) // block_size
        return min(exact, self.window // block_size) + \
            -(-summary_rows // block_size)


class CachePlan(NamedTuple):
    """A request's table — `table_width` entries, the first
    `window_blocks` of them a window's, and `ring_blocks` more behind
    them — and the groups that partition the model's layers."""

    block_size: int
    table_width: int
    window_blocks: int
    ring_blocks: int
    slots: int                 # decode slots: what sizes a ring's pool and
    #                            arrays by slot
    groups: tuple

    @property
    def num_layers(self) -> int:
        return sum(len(g.layers) for g in self.groups)

    @property
    def by_slot(self) -> tuple:
        """The layers that keep arrays by slot, in order."""
        return tuple(sorted(i for g in self.groups if g.keeps == "slots"
                            for i in g.layers))


def ring_blocks_for(window: int, prefill_chunk: int, block_size: int) -> int:
    """Blocks of a sliding layer's ring: the window and one prefill
    chunk — the chunk's rows are written before its oldest query has
    attended the window behind it — in whole blocks."""
    return -(-(window + prefill_chunk) // block_size)


_EVA = dict(
    no_prefix_cache=(
        "prefix_cache=True over summarised windows: a shared "
        "prefix would have to keep a closed window's summary "
        "rows AND the open window's exact blocks alive for "
        "its followers, and only whole blocks of exact rows "
        "are hashed today; pass prefix_cache=False"),
    no_sessions=(
        "sessions over summarised windows: a pin would have to "
        "hold the open window's exact blocks and every summary "
        "row of the conversation, and the next turn resume inside "
        "a window; only whole tables of exact rows are pinned "
        "today"),
    refuses=("prefix_cache",))
_LATENT = dict(
    no_prefix_cache=(
        "prefix_cache=True over latent rows: a shared block "
        "would be read by the expanded path in one request's "
        "prefill and by the absorbed path in another's decode, "
        "and the prefix cache's bitwise pins are not proven "
        "across the two; pass prefix_cache=False"),
    no_sessions=(
        "sessions over latent rows: a pin keeps rows that decode "
        "wrote, and the next turn's prefill would expand them "
        "beside rows of its own; not proven, so not offered"),
    no_mesh=(
        "a mesh of {n} devices over latent rows "
        "and routed experts: one row serves every head, so "
        "the head split of the pool does not apply, and an "
        "expert layer that holds a share of the experts is "
        "not built; serve it on one device"),
    refuses=("prefix_cache", "quantized", "mesh"),
    refusal=("a cache of latent rows is dense, on one device, with no "
             "prefix cache and no window"))
_GROUPED = dict(
    no_prefix_cache=(
        "prefix_cache=True over grouped rows with sliding "
        "layers: a shared prefix's rows in a ring are "
        "overwritten as its first holder goes on, so only the "
        "full layers' blocks could be shared; pass "
        "prefix_cache=False"),
    no_mesh=(
        "a mesh of {n} devices over grouped rows "
        "and a share of the experts: the all-to-all between "
        "the chips that share a layer is not built; serve "
        "one chip's share on one device"))
_RING = dict(
    _GROUPED,
    no_sessions=(
        "sessions over grouped rows with sliding layers: a pin "
        "would have to keep the ring's rows as the last turn left "
        "them and the next turn resume inside it; only whole "
        "tables of exact rows are pinned today"),
    refuses=("prefix_cache", "quantized", "mesh"),
    refusal=("a cache of two groups of layers is dense, on one device, "
             "with no prefix cache and one kind of row"))
_STATE = dict(
    no_prefix_cache=(
        "prefix_cache=True over layers with a state: a request "
        "that shares a prefix's blocks would also need the "
        "state as it stood at the prefix's last block "
        "boundary, and nothing stores such a snapshot; pass "
        "prefix_cache=False"),
    no_sessions=(
        "sessions over layers with a state: a pin would have to "
        "keep the state as the last turn left it beside the rows, "
        "and a slot's state is zeroed when the next request is "
        "seated; nothing stores a snapshot"),
    no_mesh=(
        "a mesh of {n} devices over layers with "
        "a state: the split of the state's heads and of the "
        "convolution's channels over the model axis is not "
        "built; serve it on one device"),
    refuses=("prefix_cache", "quantized", "mesh"),
    refusal=("a cache with a group of layers that keep a state a slot "
             "is dense, on one device, with no prefix cache, one kind "
             "of row and no ring"))


def cache_plan(spec, cfg, c) -> CachePlan:
    """What the layers of a model with layer spec `spec` and config
    `cfg` keep when served under the ServeConfig `c`, and the table that
    addresses it.  Groups whose reasons the engine raises first come
    first: layers with a state before the rows beside them."""
    layers, bs = int(cfg.num_layers), int(c.block_size)
    max_seq_len = int(c.max_seq_len or cfg.max_seq_len)
    table_width = -(-max_seq_len // bs)
    if table_width < 1:
        raise ValueError(f"table_width must be >= 1, got {table_width}")
    heads = spec.kv_heads or cfg.num_heads
    pair = (("k", heads, cfg.head_dim), ("v", heads, cfg.head_dim))
    window_blocks = ring_blocks = 0
    index = spec.index_layers(layers)
    if bool(index) != bool(spec.index_width) or (
            index and spec.attention != "latent"):
        raise ValueError(
            f"index keys of {spec.index_width} values are kept beside "
            f"latent rows, in some layers ({sorted(index)})")
    state, rows = spec.state_layers(layers), spec.row_layers(layers)
    bare = tuple(i for i in range(layers)
                 if i not in state and i not in rows)
    if bare and not state:
        raise ValueError(
            f"layers that own nothing ({sorted(bare)}) "
            f"stand beside layers with a state, and are none of them "
            f"({sorted(state)})")
    if spec.attention == "eva":
        # the window's exact blocks, then one summary row per chunk
        window_blocks = -(-spec.window // bs)
        table_width = window_blocks + -(-table_width // bs)
        groups = [LayerGroup(
            "eva", rows, arrays=pair, run="window",
            window=window_blocks * bs, refusal=(
                "a windowed cache takes no prefix cache and needs table "
                "entries for its summary blocks beyond the window's "
                f"{window_blocks}"), **_EVA)]
    elif spec.attention == "latent":
        row = ("row", 1, spec.latent_width)
        groups = [LayerGroup("latent", rows, arrays=(row,), **_LATENT)]
        if index:
            # the layers that choose the rows their queries attend keep an
            # index key a token beside the row; the others take the choice
            topk = min(spec.index_topk, table_width * bs)
            groups = [
                LayerGroup("sparse", index, topk=topk, arrays=(
                    row, ("key", 1, spec.index_width)), **_LATENT),
                LayerGroup("sparse", tuple(i for i in rows if i not in index),
                           topk=topk, arrays=(row,), **_LATENT)]
    elif spec.attention == "grouped":
        # a sliding layer's rows: a ring of the window and one prefill
        # chunk a request; the table's own run under a mask where that is
        # no shorter, or where layers with a state stand beside them
        window = max(spec.layer_windows, default=0)
        sliding = tuple(i for i in rows if spec.window_of(i))
        ring = ring_blocks_for(window, c.prefill_chunk, bs)
        why = {} if state else _GROUPED
        if window and ring < table_width and not state:
            if not sliding:
                raise ValueError(
                    f"a ring of {ring * bs} rows must be whole blocks of "
                    f"{bs}, for some layers ({sorted(sliding)}) "
                    f"and max_requests >= 1 requests ({c.max_batch})")
            ring_blocks = ring
            groups = [LayerGroup("grouped", sliding, keeps="ring",
                                 arrays=pair, run="ring", window=window,
                                 **_RING)]
        else:
            groups = [LayerGroup("grouped", sliding, arrays=pair,
                                 window=window, **why)]
        groups.insert(0, LayerGroup(
            "grouped", tuple(i for i in rows if i not in sliding),
            arrays=pair, **why))
    else:
        groups = [LayerGroup("paged", rows, arrays=pair)]
    if state:
        groups.insert(0, LayerGroup(
            spec.state_mixer, state, keeps="slots", run="",
            arrays=spec.state_shapes, **_STATE))
        groups.append(LayerGroup("bare", bare, keeps="nothing", run=""))
    return CachePlan(bs, table_width, window_blocks, ring_blocks,
                     int(c.max_batch),
                     tuple(g for g in groups if g.layers))


# -- what the engine asks and counts, kind by kind ---------------------------


class Counted(NamedTuple):
    """What a kind's counting reads, fixed when the engine is built: the
    plan, the plan's groups of the kind and, group by group, whether a
    decode step's and a prefill chunk's call walks what is live (`walks`,
    a pair; None where it is not asked) and the device bytes it keeps
    (`nbytes`: all of them, and those of its layers' first arrays)."""

    plan: CachePlan
    groups: tuple
    walks: tuple
    nbytes: tuple


class Kind(NamedTuple):
    """One kind of layer group.  `asks(group, spec, cfg, schedule, entry,
    q_len, batch)` answers (registry op, info) of the call over the group
    — `entry` the group's entry of `caches` — whose choice of
    implementation the engine asks the registry for once at build
    ("pallas": it walks what is live), or None; `step(counted, held)`
    counts a decode step whose lanes reach `held` [n] rows each,
    `chunk(counted, held, n_queries)` a prefill chunk that reaches `held`
    [1] rows, its padded tail's, with `n_queries` queries."""

    asks: object = None
    step: object = None
    chunk: object = None


def _row_dtype(entry):
    return jax.tree_util.tree_leaves(entry[0])[0].dtype


def _ask_paged(g, spec, cfg, s, entry, q_len, batch):
    from .layers import paged_info

    return "paged_attention", paged_info(cfg, s, q_len, _row_dtype(entry))


def _ask_eva(g, spec, cfg, s, entry, q_len, batch):
    from .layers import eva_info

    return "eva_attention", eva_info(spec, cfg, s, q_len, _row_dtype(entry))


def _ask_latent(g, spec, cfg, s, entry, q_len, batch):
    from .layers import latent_info

    return "latent_attention", latent_info(
        cfg, s, q_len, _row_dtype(entry), spec.latent_width)


def _ask_grouped(g, spec, cfg, s, entry, q_len, batch):
    # a full layer and a sliding one are asked apart: a sliding layer's
    # rows are a ring, or the table under a window; and a decode step
    # apart from a prefill chunk, one request's
    from .layers import grouped_info

    return "grouped_attention", grouped_info(
        spec, cfg, s, q_len, _row_dtype(entry), g.window, g.run == "ring",
        batch)


def _ask_state(g, spec, cfg, s, entry, q_len, batch):
    kernel = STATE_MIXERS[g.kind].step_kernel
    return kernel(spec, entry) if kernel is not None else None


def _table_rows_walked(c: Counted, held) -> int:
    """The pool rows a step FETCHES for lanes that reach `held` rows:
    their live blocks where the call is the walk, the table's whole
    width where it gathers."""
    bs = c.plan.block_size
    if c.walks[0][0]:
        return int((-(-held // bs) * bs).sum())
    return len(held) * c.plan.table_width * bs


def _count_paged(c: Counted, held) -> None:
    COUNTERS.add("serve.paged.rows_walked", calls=len(held),
                 nbytes=_table_rows_walked(c, held))


def _count_latent(c: Counted, held) -> None:
    COUNTERS.add("serve.mla.rows_read", calls=len(held),
                 nbytes=int(held.sum()))
    COUNTERS.add("serve.mla.rows_walked", calls=len(held),
                 nbytes=_table_rows_walked(c, held))


def _count_eva(c: Counted, held) -> None:
    """What a query at p reads: its window up to itself and the
    summaries of the windows closed before it; what its attention
    fetches for that: the blocks those rows lie in, or every entry of
    the table."""
    p, n = held - 1, len(held)
    W, C = c.groups[0].window, c.plan.block_size
    COUNTERS.add("serve.eva.rows_read", calls=n,
                 nbytes=int((p % W + 1 + p // W * (W // C)).sum()))
    COUNTERS.add("serve.eva.context_tokens", calls=n, nbytes=int(held.sum()))
    COUNTERS.add("serve.eva.rows_walked", calls=n, nbytes=C * (
        int(sum(live_blocks(p, W, C, C)).sum()) if c.walks[0][0]
        else n * c.plan.table_width))


def _count_sparse(c: Counted, held) -> None:
    n, topk = len(held), c.groups[0].topk
    full = sum(len(g.layers) for g in c.groups if len(g.arrays) == 2)
    every = sum(len(g.layers) for g in c.groups)
    COUNTERS.add("serve.sparse.keys_scored", calls=n * full,
                 nbytes=int(held.sum()) * full)
    COUNTERS.add("serve.sparse.rows_selected", calls=n * every,
                 nbytes=every * int(np.minimum(held, topk).sum()))
    COUNTERS.add("serve.sparse.rows_fetched", calls=n * every,
                 nbytes=n * every * topk)
    COUNTERS.add("serve.sparse.selections_shared", calls=every - full)


def _count_sparse_chunk(c: Counted, held, n_queries: int) -> None:
    shared = sum(len(g.layers) for g in c.groups if len(g.arrays) == 1)
    if shared:
        COUNTERS.add("serve.sparse.selections_shared", calls=shared)


def _grouped_rows_fetched(c: Counted, held, chunk: int,
                          n_queries: int) -> int:
    """The pool rows the layers with grouped rows FETCH for calls that
    reach `held` [n] rows each with their last `n_queries` positions as
    queries — decoded slots (`chunk` 0), or the one request of a prefill
    chunk (1), its padded tail counted: a call's live blocks where the
    group walks — from the table's first entry in a full layer, from the
    block of the OLDEST query's lower bound `held - n_queries - window +
    1` in a sliding one (kernels/paged.py `_sliding_run`: a decode
    step's one query, a chunk's first), the run at most — every entry of
    its run — the table, or the ring — where it gathers."""
    bs, total = c.plan.block_size, 0
    for g, walks in zip(c.groups, c.walks):
        run = bs * (c.plan.ring_blocks if g.run == "ring"
                    else c.plan.table_width)
        if not walks[chunk]:
            rows = run * len(held)
        else:
            first = np.maximum(held - n_queries - g.window + 1, 0) // bs \
                if g.window else 0
            rows = int(np.minimum((-(-held // bs) - first) * bs, run).sum())
        total += len(g.layers) * rows
    return total


def _count_grouped(c: Counted, held) -> None:
    """What a step's queries attend — the window's rows in a sliding
    layer, every cached row in a full one — and what is fetched for
    that."""
    n, read = len(held), 0
    for g in c.groups:
        rows = int(np.minimum(held, g.window).sum() if g.window
                   else held.sum())
        if g.window:
            COUNTERS.add("serve.window.rows_read", calls=n, nbytes=rows)
        read += len(g.layers) * rows
    COUNTERS.add("serve.attn.rows_read", calls=n, nbytes=read)
    COUNTERS.add("serve.attn.rows_walked", calls=n,
                 nbytes=_grouped_rows_fetched(c, held, 0, 1))


def _count_grouped_chunk(c: Counted, held, n_queries: int) -> None:
    COUNTERS.add("serve.attn.prefill_rows_walked",
                 nbytes=_grouped_rows_fetched(c, held, 1, n_queries))


def _count_state(c: Counted, held) -> None:
    """What the step's program as built streams: every slot's arrays,
    twice — less, where the kind's kernel walks the live slots, the first
    array (the float32 state) of each slot that is not running."""
    (group,), ((every, first),) = c.groups, c.nbytes
    family = STATE_MIXERS[group.kind].counters
    dead = 2 * first // c.plan.slots if c.walks[0][0] else 0
    COUNTERS.add(f"{family}.state_bytes",
                 nbytes=2 * every - dead * (c.plan.slots - len(held)))
    COUNTERS.add(f"{family}.slots_live",
                 nbytes=len(held) * len(group.layers))


KINDS = {
    "paged": Kind(_ask_paged, _count_paged),
    "eva": Kind(_ask_eva, _count_eva),
    "latent": Kind(_ask_latent, _count_latent),
    # (a learned selection gathers the rows it chose: no walk to ask about)
    "sparse": Kind(None, _count_sparse, _count_sparse_chunk),
    "grouped": Kind(_ask_grouped, _count_grouped, _count_grouped_chunk),
    "bare": Kind(),
    **{kind: Kind(_ask_state, _count_state) for kind in STATE_MIXERS},
}


# -- the cache ----------------------------------------------------------------


class PagedKVCache:
    """Device arrays + host allocator for one serving engine, laid out
    as `plan` (a `CachePlan`) says.

    `caches` is the functional state the jitted programs thread: one
    entry a layer, what its group keeps.  The engine passes it into a
    program and stores the returned (donated) arrays back; this object
    owns the allocator book-keeping only.

    Owners are opaque hashable keys: the scheduler uses request rids,
    the session store uses `("session", sid)` tuples — both walk the
    same refcount/free paths.
    """

    def __init__(self, plan: CachePlan, num_blocks: int, dtype=jnp.float32,
                 mesh_info=None, prefix_cache: bool = True,
                 min_match_blocks: int = 1, prefix_salt: str = ""):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved trash "
                f"block), got {num_blocks}")
        if int(min_match_blocks) < 1:
            raise ValueError(
                f"min_match_blocks must be >= 1, got {min_match_blocks}")
        self.plan = plan
        self.num_layers = plan.num_layers
        self.num_blocks = int(num_blocks)
        self.block_size = plan.block_size
        self.table_width = plan.table_width
        self.window_blocks = plan.window_blocks
        self.window_tokens = plan.window_blocks * plan.block_size
        self.ring_blocks = plan.ring_blocks
        self.ring_tokens = plan.ring_blocks * plan.block_size
        self.ring_pool_blocks = plan.slots * plan.ring_blocks + 1
        self.dtype = dtype
        mode, self.dense_dtype = resolve_kv_dtype(dtype)
        # "int8"/"int4" when blocks are stored quantized, else None
        self.quant_wire = mode if mode in KV_QUANT_WIRES else None
        by_block = [g for g in plan.groups if g.keeps == "blocks"]
        self._sharding = self._kv_sharding(
            mesh_info, by_block[0].arrays[0][1]) if by_block else None
        asked = {"prefix_cache": bool(prefix_cache),
                 "quantized": mode != "dense",
                 "mesh": mesh_info is not None and mesh_info.size > 1}
        for g in plan.groups:
            if any(asked[what] for what in g.refuses):
                raise ValueError(g.refusal)
        for g in by_block:
            odd = [w for _, _, w in g.arrays if w % 2]
            if self.quant_wire == "int4" and odd:
                raise ValueError(
                    f"int4 KV packs two codes per byte and needs an even "
                    f"head_dim, got {odd[0]}")
        kinds = [g.kind for g in plan.groups if g.keeps == "slots"]
        # the mixers' own family of counters: serve.ssm | serve.gdn |
        # serve.conv
        self._reset_counter = f"{STATE_MIXERS[kinds[0]].counters}." \
            f"state_resets" if kinds else ""
        self._reset_fn = None                     # lazy jitted slot zeroing
        self.by_slot = plan.by_slot               # the layers it zeroes
        self.caches = [None] * plan.num_layers
        for g in plan.groups:
            for i in g.layers:
                self.caches[i] = self._entry(g)
        # block 0 reserved as trash; LIFO free list so the fragmentation
        # tests exercise immediate reuse of just-freed blocks
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._owned: Dict[Any, List[int]] = {}
        # holders per block (live requests + session pins); absent = 0
        self._ref: Dict[int, int] = {}
        # owners on a "window" run: rid -> [table, booked blocks, closed
        # windows]
        self._booked: Dict[Any, list] = {}
        # the ring's free list (block 0 its trash block) and rid -> the
        # request's table [life | ring], rewritten in place
        self._ring_free: List[int] = list(
            range(self.ring_pool_blocks - 1, 0, -1)) \
            if self.ring_blocks else []
        self._ring: Dict[Any, np.ndarray] = {}
        self.evictions = 0
        # -- prefix cache state ---------------------------------------
        self.prefix_enabled = bool(prefix_cache)
        self.min_match_blocks = int(min_match_blocks)
        mode_name = self.quant_wire or jnp.dtype(self.dense_dtype).name
        self._salt = hashlib.blake2b(
            f"{prefix_salt}|{mode_name}|{self.block_size}".encode(),
            digest_size=16).digest()
        self._hash_index: Dict[bytes, int] = {}   # chain hash -> block
        self._block_hash: Dict[int, bytes] = {}   # block -> chain hash
        # refcount-0 registered blocks, oldest first (the eviction order)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.cow_copies = 0
        self.prefix_evictions = 0
        self._copy_fn = None                      # lazy jitted block copy

    # -- device state -------------------------------------------------

    def _kv_sharding(self, mesh_info, num_heads: int):
        """Heads sharded over the TP `model` axis when a mesh is in
        scope and divides them; None otherwise (plain local arrays).
        For the flat pool that is a split of the row's columns, which
        falls on head boundaries when the row has no padding lanes (a
        padded row still shards, and the partitioner moves the lanes
        that land on another rank than their head's).  Scales are
        [rows, H]: the same head split as the payload."""
        if mesh_info is None:
            return None
        from ..comm.mesh import MODEL_AXIS

        tp = mesh_info.axis_size(MODEL_AXIS)
        if tp <= 1:
            return None
        if num_heads % tp:
            from ..utils.logging import logger

            logger.warning(
                f"serving KV cache: model axis {tp} does not divide "
                f"num_heads {num_heads}; cache stays unsharded")
            return None
        return mesh_info.sharding(None, MODEL_AXIS)

    def _entry(self, g: LayerGroup) -> tuple:
        """One layer's entry of `caches`, zeroed, as its group keeps it."""
        if g.keeps == "slots":
            return tuple(jnp.zeros((self.plan.slots,) + tuple(a_slot),
                                   dtype or self.dense_dtype)
                         for a_slot, dtype in g.arrays)
        rows = self.block_size * (
            self.ring_pool_blocks if g.keeps == "ring" else self.num_blocks)
        placed = lambda z: (z if self._sharding is None
                            else jax.device_put(z, self._sharding))
        if self.quant_wire is None:
            return tuple(
                placed(jnp.zeros((rows, pool_width(heads, width)),
                                 self.dense_dtype))
                for _, heads, width in g.arrays)
        # zero payload + zero scale dequantizes to exact zero, matching
        # the dense cache's zero init
        int8 = self.quant_wire == "int8"
        return tuple(
            (placed(jnp.zeros(
                (rows, pool_width(heads, width if int8 else width // 2)),
                jnp.int8 if int8 else jnp.uint8)),
             placed(jnp.zeros((rows, heads), jnp.float16)))
            for _, heads, width in g.arrays)

    def group_nbytes(self, g: LayerGroup) -> tuple:
        """Device bytes of the group's layers: (all their arrays', their
        first arrays')."""
        size = lambda tree: sum(int(a.size) * a.dtype.itemsize
                                for a in jax.tree_util.tree_leaves(tree))
        return (sum(size(self.caches[i]) for i in g.layers),
                sum(size(self.caches[i][:1]) for i in g.layers))

    def nbytes(self) -> int:
        return sum(self.group_nbytes(g)[0] for g in self.plan.groups)

    def state_nbytes(self) -> int:
        """Device bytes of the layers that keep a state a slot: all
        slots', whatever is seated."""
        return sum(self.group_nbytes(g)[0] for g in self.plan.groups
                   if g.keeps == "slots")

    def index_nbytes(self) -> int:
        """Device bytes of the index keys: the second array of the
        layers that select the rows their queries attend."""
        return sum(int(self.caches[i][n].size)
                   * self.caches[i][n].dtype.itemsize
                   for g in self.plan.groups if g.keeps == "blocks"
                   for n, a in enumerate(g.arrays) if a[0] == "key"
                   for i in g.layers)

    def bytes_per_block(self) -> int:
        """Device bytes one block costs across all layers with rows (K
        and V)."""
        return (self.nbytes() - self.state_nbytes()) // self.num_blocks

    def reset_state(self, slot: int) -> None:
        """Zero slot `slot`'s entries of every layer that keeps arrays by
        slot, on the device and in place: behind every program already
        launched, before any launched after; counted under the mixers'
        `state_resets`."""
        if self._reset_fn is None:
            self._reset_fn = jax.jit(
                lambda states, slot: jax.tree_util.tree_map(
                    lambda a: a.at[slot].set(0), states),
                donate_argnums=(0,))
        states = self._reset_fn([self.caches[i] for i in self.by_slot],
                                np.int32(slot))
        for i, entry in zip(self.by_slot, states):
            self.caches[i] = entry
        COUNTERS.add(self._reset_counter)

    # -- allocator ----------------------------------------------------

    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (the trash block is not capacity)."""
        return self.num_blocks - 1

    @property
    def blocks_in_use(self) -> int:
        """Blocks with a live holder (request or session pin).
        Refcount-0 cached blocks parked in the LRU are NOT in use —
        they are reclaimable the moment an allocation needs them."""
        return self.capacity_blocks - len(self._free) - len(self._lru)

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: the free list plus the refcount-0
        cached blocks the LRU would evict to serve an allocation, less
        what requests on a "window" run have booked and not yet taken."""
        return len(self._free) + len(self._lru) - self.promised_blocks

    @property
    def promised_blocks(self) -> int:
        """Blocks booked by `reserve` that their owners do not hold
        right now (not yet written, or given back at a window close)."""
        return sum(b[1] - len(self._owned[rid])
                   for rid, b in self._booked.items())

    @property
    def token_capacity(self) -> int:
        """The longest request one table can address."""
        if not self.window_blocks:
            return self.table_width * self.block_size
        return (self.table_width - self.window_blocks) * self.block_size ** 2

    @property
    def summary_rows_in_use(self) -> int:
        """Summary rows live requests attend to: the completed chunks of
        their closed windows."""
        return sum(b[2] for b in self._booked.values()) * self.window_blocks

    @property
    def cached_blocks(self) -> int:
        """Hash-registered blocks (live holders + LRU residents)."""
        return len(self._hash_index)

    def blocks_needed(self, n_tokens: int) -> int:
        """The most blocks of the pool a request of `n_tokens` holds at
        once: what its groups book (they share block ids)."""
        return max(g.books(n_tokens, self.block_size)
                   for g in self.plan.groups)

    def _take_free(self) -> int:
        """Pop one allocatable block, evicting the coldest refcount-0
        cached block when the free list is dry.  Callers check
        `free_blocks` first; eviction never touches a live holder."""
        if self._free:
            return self._free.pop()
        block, _ = self._lru.popitem(last=False)   # oldest first
        h = self._block_hash.pop(block, None)
        if h is not None:
            self._hash_index.pop(h, None)
        self.prefix_evictions += 1
        COUNTERS.add("kv.prefix_evictions")
        return block

    def alloc(self, rid, n_blocks: int,
              shared: Optional[Sequence[int]] = None,
              privatize_last: bool = False) -> Optional[np.ndarray]:
        """Allocate `n_blocks` table entries for request `rid`; returns
        the padded block table `[table_width] int32` (unused entries
        point at the trash block) or None when the pool cannot cover
        the FRESH share.  `shared` aliases already-cached blocks (from
        `match_prefix`) as the table's leading entries — each gains a
        reference instead of costing a fresh block.  `privatize_last`
        handles the whole-prompt-cached case, where prefill must
        rewrite the final prompt token inside the last shared block:
        a refcount-0 (LRU) block is adopted in place, a live-shared
        block is copied to a private block first (copy-on-write).  On a
        "window" run nothing is handed out: the blocks are booked
        (`reserve`)."""
        if self.window_blocks:
            return self.reserve(rid, n_blocks)
        n_blocks = int(n_blocks)
        shared = list(shared or ())
        if rid in self._owned:
            raise ValueError(f"request {rid} already holds blocks")
        if n_blocks > self.table_width:
            raise ValueError(
                f"request {rid} needs {n_blocks} blocks > table width "
                f"{self.table_width} (engine capacity "
                f"{self.table_width * self.block_size} tokens)")
        if len(shared) > n_blocks:
            raise ValueError(
                f"request {rid}: {len(shared)} shared blocks exceed the "
                f"{n_blocks}-block table")
        cow = (privatize_last and bool(shared)
               and self._ref.get(shared[-1], 0) > 0)
        fresh = n_blocks - len(shared) + (1 if cow else 0)
        # `free_blocks` counts LRU-parked refcount-0 residents as
        # allocatable — but the matched blocks can BE those residents
        # (including the adopt-in-place last block).  Aliasing removes
        # them from the LRU, so they must not also be counted as
        # capacity for the fresh share, or _take_free would drain an
        # empty pool mid-allocation.
        lru_shared = sum(1 for b in set(shared)
                         if self._ref.get(b, 0) == 0)
        if fresh > self.free_blocks - lru_shared:
            return None
        blocks: List[int] = []
        cow_pair = None
        for i, b in enumerate(shared):
            if privatize_last and i == len(shared) - 1:
                if self._ref.get(b, 0) == 0:
                    # sole cached holder: adopt the block in place (it
                    # keeps its hash — the rewrite of the final prompt
                    # token is bitwise-identical by the chunk-invariance
                    # pin, so the registration stays truthful)
                    self._lru.pop(b, None)
                    self._ref[b] = 1
                    blocks.append(b)
                else:
                    nb = self._take_free()
                    self._ref[nb] = 1
                    cow_pair = (b, nb)
                    blocks.append(nb)
                continue
            if self._ref.get(b, 0) == 0:
                self._lru.pop(b, None)
            self._ref[b] = self._ref.get(b, 0) + 1
            blocks.append(b)
        for _ in range(n_blocks - len(shared)):
            nb = self._take_free()
            self._ref[nb] = 1
            blocks.append(nb)
        self._owned[rid] = blocks
        if cow_pair is not None:
            self._cow_copy(*cow_pair)
        table = np.full((self.table_width + self.ring_blocks,),
                        TRASH_BLOCK, np.int32)
        table[:n_blocks] = blocks
        if self.ring_blocks:
            self._ring[rid] = table
            return table.copy()  # the booked one never leaves: `extend`
        return table

    def blocks_of(self, rid) -> List[int]:
        return list(self._owned.get(rid, ()))

    def free(self, rid, evicted: bool = False) -> int:
        """Drop `rid`'s references.  A block whose refcount reaches
        zero returns to the free list — unless it is hash-registered,
        in which case it parks in the LRU (still matchable, reclaimed
        only under pressure).  `evicted=True` marks a FORCED reclaim
        (shed/errored request) and bumps `kv.evictions` for every block
        actually released (a still-shared block survives its evicted
        holder); natural completion does not."""
        blocks = self._owned.pop(rid, None)
        self._booked.pop(rid, None)
        ring = self._ring.pop(rid, None)
        if ring is not None:
            self._ring_free.extend(
                int(b) for b in ring[self.table_width:] if b != TRASH_BLOCK)
        if not blocks:
            return 0
        released = 0
        for b in reversed(blocks):
            r = self._ref.get(b, 1) - 1
            if r > 0:
                self._ref[b] = r
                continue
            self._ref.pop(b, None)
            released += 1
            if b in self._block_hash:
                self._lru[b] = None            # park at the MRU end
            else:
                self._free.append(b)
        if evicted and released:
            self.evictions += released
            COUNTERS.add("kv.evictions", calls=released)
        return len(blocks)

    # -- runs whose blocks are taken as positions are written ----------

    def reserve(self, rid, n_blocks: int) -> Optional[np.ndarray]:
        """Book `n_blocks` (the request's bounded footprint) without
        taking any: -> an all-trash table, or None when the pool cannot
        promise them."""
        if rid in self._owned:
            raise ValueError(f"request {rid} already holds blocks")
        if int(n_blocks) > self.free_blocks:
            return None
        table = np.full((self.table_width,), TRASH_BLOCK, np.int32)
        self._owned[rid] = []
        self._booked[rid] = [table, int(n_blocks), 0]
        return table.copy()  # the booked one never leaves: see `extend`

    def _take_into(self, rid, table, entry: int) -> bool:
        if table[entry] != TRASH_BLOCK:
            return False
        b = self._take_free()
        self._ref[b] = 1
        self._owned[rid].append(b)
        table[entry] = b
        return True

    def extend(self, rid, start: int, stop: int) -> Optional[np.ndarray]:
        """Before positions [start, stop) are written: take the blocks
        they need of the runs that are not handed out at admission.
        Never fails: a window's blocks were booked, a ring's pool holds
        every slot's.  -> a copy of the table as it now stands, or None
        where it stands as it did (nothing was taken: always, where every
        run is a "life" run).

        A copy, because the booked table is rewritten in place here and
        in `close_window`, and a program that was handed it runs after
        its caller has returned (on the CPU `jnp.asarray` may alias a
        host array, not copy it): a prefill chunk that filled the window
        then read the table after `close_window` had trashed it.

        "window": the exact blocks of those window offsets and the
        summary blocks of the chunks the positions complete (counted:
        `kv.summary_rows`).  "ring": the blocks that hold rows `p % ring`
        of those positions, where the request's run does not have them
        yet."""
        bs, took = self.block_size, False
        if self.ring_blocks:
            table, rb = self._ring[rid], self.ring_blocks
            last = min(-(-int(stop) // bs), int(start) // bs + rb)
            for blk in range(int(start) // bs, last):
                entry = self.table_width + blk % rb
                if table[entry] == TRASH_BLOCK:
                    table[entry] = self._ring_free.pop()
                    took = True
        elif self.window_blocks:
            table, wb = self._booked[rid][0], self.window_blocks
            for blk in range(int(start) // bs, -(-int(stop) // bs)):
                took |= self._take_into(rid, table, blk % wb)
            for chunk in range(int(start) // bs, int(stop) // bs):
                took |= self._take_into(rid, table, wb + chunk // bs)
            if int(stop) // bs > int(start) // bs:
                COUNTERS.add("kv.summary_rows",
                             nbytes=int(stop) // bs - int(start) // bs)
        return table.copy() if took else None

    def window_full(self, cached_len: int) -> bool:
        """Whether a request that has written `cached_len` rows has just
        filled its open window (never, without a "window" run)."""
        return bool(self.window_tokens) and \
            cached_len % self.window_tokens == 0

    def close_window(self, rid) -> int:
        """The request's open window is full: its exact blocks go back
        to the free list, its summary rows stay and become visible.
        -> blocks returned."""
        booked = self._booked[rid]
        table = booked[0]
        mine = self._owned[rid]
        back = [int(b) for b in table[:self.window_blocks]
                if b != TRASH_BLOCK]
        for b in back:
            self._ref.pop(b, None)
            mine.remove(b)
            self._free.append(b)
        table[:self.window_blocks] = TRASH_BLOCK
        booked[2] += 1
        COUNTERS.add("kv.window_closes", nbytes=len(back))
        return len(back)

    def count_wraps(self, cached_len: int) -> None:
        """A request ends with `cached_len` rows written: count the
        blocks a ring saved it (`kv.ring_wraps`), where it held more rows
        than a ring."""
        saved = -(-cached_len // self.block_size) - self.ring_blocks
        if self.ring_blocks and saved > 0:
            COUNTERS.add("kv.ring_wraps", nbytes=saved)

    def ring_blocks_of(self, rid) -> List[int]:
        """The ring's blocks the request holds."""
        table = self._ring.get(rid)
        if table is None:
            return []
        return [int(b) for b in table[self.table_width:]
                if b != TRASH_BLOCK]

    @property
    def ring_blocks_in_use(self) -> int:
        """The ring's blocks with a holder."""
        return self.ring_pool_blocks - 1 - len(self._ring_free)

    def exact_blocks_of(self, rid) -> List[int]:
        if rid not in self._booked:
            return self.blocks_of(rid)
        table = self._booked[rid][0]
        return [int(b) for b in table[:self.window_blocks]
                if b != TRASH_BLOCK]

    def summary_blocks_of(self, rid) -> List[int]:
        if rid not in self._booked:
            return []
        table = self._booked[rid][0]
        return [int(b) for b in table[self.window_blocks:]
                if b != TRASH_BLOCK]

    # -- prefix cache -------------------------------------------------

    def prefix_hashes(self, tokens: Sequence[int]) -> List[bytes]:
        """Chain hashes of `tokens`' FULL blocks: `h_i = H(h_{i-1},
        block_i_tokens)`, seeded with the (model, kv storage mode,
        block size) salt.  The partial tail block is never hashed —
        only immutable, full blocks are shareable."""
        if not self.prefix_enabled:
            return []
        bs = self.block_size
        out: List[bytes] = []
        h = self._salt
        for i in range(len(tokens) // bs):
            blk = np.asarray(tokens[i * bs:(i + 1) * bs], np.int64)
            h = hashlib.blake2b(h + blk.tobytes(),
                                digest_size=16).digest()
            out.append(h)
        return out

    def match_prefix(self, hashes: Sequence[bytes]) -> List[int]:
        """The longest registered prefix of `hashes` -> block ids.
        Matches shorter than `min_match_blocks` return empty (below
        that, aliasing buys less than its book-keeping costs)."""
        if not self.prefix_enabled:
            return []
        blocks: List[int] = []
        for h in hashes:
            b = self._hash_index.get(h)
            if b is None:
                break
            blocks.append(b)
        if len(blocks) < self.min_match_blocks:
            return []
        return blocks

    def register_prefix(self, rid, hashes: Sequence[bytes],
                        start: int = 0) -> int:
        """Publish `rid`'s blocks `start..len(hashes)-1` under their
        chain hashes (first registration wins — a concurrent identical
        prompt keeps the incumbent).  Only prefill-written rows are
        pinned bitwise against recomputation, so only blocks from a
        pure-prefill chain are safe to publish: a request that adopted
        decode-written rows (session pins) must not call this at all —
        everything it prefills attends over those rows.  `start` skips
        the leading blocks that are already registered (the matched
        prefix)."""
        if not self.prefix_enabled:
            return 0
        blocks = self._owned.get(rid)
        if not blocks:
            return 0
        n = 0
        for i in range(int(start), min(len(hashes), len(blocks))):
            h = hashes[i]
            if h in self._hash_index:
                continue
            b = blocks[i]
            old = self._block_hash.get(b)
            if old is not None and old != h:
                continue
            self._hash_index[h] = b
            self._block_hash[b] = h
            n += 1
        return n

    def _cow_copy(self, src: int, dst: int) -> None:
        """Device row copy of one block (every layer, K and V, payload
        and scales) — the copy-on-write servicing a write into a
        live-shared block."""
        if self._copy_fn is None:
            def fn(caches, src_rows, dst_rows):
                return jax.tree_util.tree_map(
                    lambda a: a.at[dst_rows].set(a[src_rows]), caches)

            self._copy_fn = jax.jit(fn, donate_argnums=(0,))
        bs = self.block_size
        rows = np.arange(bs, dtype=np.int32)
        self.caches = self._copy_fn(self.caches,
                                    jnp.asarray(rows + src * bs),
                                    jnp.asarray(rows + dst * bs))
        self.cow_copies += 1
        COUNTERS.add("kv.cow_copies", nbytes=self.bytes_per_block())

    # -- session pins -------------------------------------------------

    def pin(self, owner, rid) -> int:
        """Take one extra reference on `rid`'s blocks under `owner` (a
        session key) so they survive the request's `free()` — the
        resident-session mechanism.  Returns the pinned block count."""
        if owner in self._owned:
            raise ValueError(f"owner {owner!r} already holds blocks")
        blocks = self._owned.get(rid)
        if not blocks:
            return 0
        for b in blocks:
            self._ref[b] = self._ref.get(b, 0) + 1
        self._owned[owner] = list(blocks)
        return len(blocks)

    def alloc_from_pin(self, rid, n_blocks: int,
                       pin_owner) -> Optional[np.ndarray]:
        """Transfer a session pin's blocks to request `rid` wholesale
        (references move, nothing is copied — the pin's partial tail
        block arrives private and writable) and top up with fresh
        blocks to `n_blocks`.  Returns the table, or None (pin left
        intact) when the fresh share cannot be covered."""
        if rid in self._owned:
            raise ValueError(f"request {rid} already holds blocks")
        blocks = self._owned.get(pin_owner)
        if not blocks:
            return None
        n_blocks = max(int(n_blocks), len(blocks))
        if n_blocks > self.table_width:
            raise ValueError(
                f"request {rid} needs {n_blocks} blocks > table width "
                f"{self.table_width}")
        fresh = n_blocks - len(blocks)
        if fresh > self.free_blocks:
            return None
        self._owned.pop(pin_owner)
        blocks = list(blocks)
        for _ in range(fresh):
            nb = self._take_free()
            self._ref[nb] = 1
            blocks.append(nb)
        self._owned[rid] = blocks
        table = np.full((self.table_width,), TRASH_BLOCK, np.int32)
        table[:n_blocks] = blocks
        return table

    # -- telemetry ----------------------------------------------------

    def sample_occupancy(self) -> None:
        """Per-step occupancy sample (mean = bytes/calls in the
        report, the input.queue_depth convention)."""
        COUNTERS.add("kv.blocks_in_use", nbytes=self.blocks_in_use)


    def describe(self) -> str:
        mode = (self.quant_wire if self.quant_wire
                else jnp.dtype(self.dense_dtype).name)
        MiB = 1 << 20
        rows = "exact rows" if not self.window_blocks else (
            f"exact rows for a window of {self.window_tokens} tok "
            f"({self.window_blocks} blocks) + summary rows 1 per "
            f"{self.block_size} tok "
            f"({self.table_width - self.window_blocks} blocks)")
        count = lambda keeps: sum(len(g.layers) for g in self.plan.groups
                                  if g.keeps == keeps)
        if count("ring"):
            rows += (f" in {count('blocks')} full "
                     f"layer(s); {count('ring')} layer(s) with a "
                     f"window in a ring of {self.ring_tokens} rows a "
                     f"request ({self.ring_pool_blocks} blocks of their "
                     f"own)")
        if count("slots"):
            rows += (f" in {count('blocks')} "
                     f"layer(s); {count('slots')} layer(s) with no "
                     f"rows and a state a slot, {self.plan.slots} slots "
                     f"({self.state_nbytes() / MiB:.2f} MiB)")
            if count("nothing"):
                rows += (f"; {count('nothing')} layer(s) with neither")
        by_block = [g for g in self.plan.groups if g.keeps == "blocks"]
        arrays = {a[0]: a for g in by_block for a in g.arrays}
        keyed = sorted(i for g in by_block for i in g.layers
                       if any(a[0] == "key" for a in g.arrays))
        return (f"PagedKVCache(layers={self.num_layers}, "
                f"blocks={self.num_blocks} x {self.block_size} rows, {rows}, "
                f"table_width={self.table_width}, " + (
                    f"one latent row of {arrays['row'][2]}, "
                    if "row" in arrays else
                    f"heads={arrays['k'][1]}, head_dim={arrays['k'][2]}, ")
                + (f"index keys of {arrays['key'][2]} in layers {keyed} "
                   f"({self.index_nbytes() / MiB:.2f} MiB), "
                   if keyed else "")
                + f"kv={mode}, "
                f"prefix_cache={'on' if self.prefix_enabled else 'off'}, "
                f"sharded={self._sharding is not None}, "
                f"{self.nbytes() / MiB:.2f} MiB)")
