"""Paged KV cache: fixed-size blocks, a refcounted free-list allocator,
per-request block tables, a block-level prefix cache, — for models
whose attention keeps exact keys only for an open window — a second kind
of row under the same allocator, and — for models some of whose layers
attend a sliding window — a second GROUP of layers with rows, free list
and table run of its own.

The serving problem the static cache in models/generation.py cannot
solve: a decode batch whose membership changes every step.  A contiguous
[B, L, H, Dh] cache ties a request's KV memory to its batch slot and its
maximum length — admitting a request mid-flight or finishing one early
strands memory.  Paging (vLLM's PagedAttention recipe, PAPERS.md) breaks
the cache into fixed-size blocks owned by a host-side free list; a
request holds exactly the blocks its current length needs, a finished
request returns them the same step, and the decode program addresses KV
through a per-request block table — so fragmentation is bounded at one
partially-filled block per request and admission is a free-list check,
not a compaction.

Prefix cache (PR 19): a FULL, immutable block's content is named by a
token-id chain hash `h_i = H(h_{i-1}, tokens_in_block_i)` salted with
the model fingerprint + kv storage mode, so two requests sharing a
prompt prefix resolve to the same hash chain.  Blocks become refcounted:
N requests alias ONE physical block by putting the same id in their
tables (the paged-attention gather cannot tell — `serving/programs.py`
is untouched on the read path, which is what keeps greedy serving
bitwise-identical to `generate()` with the cache on).  A finished
holder's registered blocks are not freed but parked in an LRU of
refcount-0 blocks: still matchable, evicted (hash deregistered, block
reused) only when the free list runs dry — never a live holder.  The
partially-filled tail block is always private (only full blocks are
hashed), and the one write that can land in a shared block — the
recompute of the final prompt token when the whole prompt is cached —
goes copy-on-write: sole registered holder is adopted in place, a
live-shared block is row-copied to a private block first
(`kv.cow_copies`).  Only prefill-written rows are ever registered;
decode-written rows (whose bitwise equality with a prefill recompute is
not pinned) stay private to their request/session.

Session pins ride the same refcounts: `pin(owner, rid)` takes one extra
reference on a finished request's blocks so a follow-up turn can adopt
them wholesale (`alloc_from_pin` transfers ownership, no copies) and
re-prefill only its new tokens.

Device layout: per layer, K and V each live in ONE array of cache
rows, `[num_blocks * block_size, pool_width(H, Dh)]`: a token's H heads
side by side in one row of `H * Dh` lanes, rounded up to the 128 lanes
the chip tiles a row in anyway (GPT-2 xl: 1,600 -> 1,664, 4 %; nothing
where `H * Dh` is a multiple of 128).  A block is then `block_size`
consecutive rows — one contiguous slab — and the programs use the pool
as it lies: the decode write is a batched row scatter at
`table[pos // bs] * bs + pos % bs`, the attention read either a row
gather of the table's blocks (the oracle) or a copy of each live block
(kernels/paged.py).  Kept as `[rows, H, Dh]` the chip stores the pool
rows-minor-most and transposes all of it for every scatter and gather
(32 ms a call at GPT-2 xl's 513 blocks, PERF.md PR 30).  On a mesh the
row's lanes are sharded over the `model` axis (the same Megatron TP
layout as the weights: a rank's columns are its heads), so each TP rank
holds its heads' share of every block and the gather/scatter stay local
to the row dimension.  A windowed cache (below) has the same row shape:
an exact row and a summary row are both `H * Dh` values side by side,
and its attention copies live blocks from the pool as the paged one
does (kernels/eva.py).  (Rows of 2 bytes share a tile's sublanes in
pairs, so a scatter of single rows rewrites its neighbours' words: a
prefill chunk that is whole blocks writes them as blocks,
serving/layers.py `_block_write`.)

Quantized storage (`dtype="int8" | "int4"`): each K/V entry becomes a
(payload, scales) pair — int8/uint8 codes `[rows, pool_width(H, Dh |
Dh/2)]` plus one fp16 scale per (row, head) `[rows, H]` through the
PR-7 row kernels
(runtime/comm/quant.py `quantize_rows`).  The scale granularity is one
row, FINER than one cache block, so a decode scatter-write touches
exactly its own rows' payload and scales (block-local, no
read-modify-write of a shared block scale) and the TP head split
shards scales `[rows, H]` alongside the payload.  The programs
dequantize gathered rows to fp32 in-program (serving/programs.py) —
at matched kv_dtype both the speculative and the plain decode path
read identical quantized rows, which is what keeps the spec-decode
parity pin exact even at int4.  Quantized rows are pure functions of
the token prefix like dense rows, so prefix aliasing stays bitwise at
int8/int4 too (the chain hash is salted with the storage mode, so a
dense block is never served to an int8 engine).

Two kinds of row (`window_tokens > 0`, the EVA layer spec): a block is
either an EXACT block (`block_size` consecutive tokens' K/V of the
request's open window) or a SUMMARY block (`block_size` consecutive
summary rows, one row per `block_size` tokens, k~ in the K array and v~
in the V array).  Both come from the one pool and the one free list; a
request's table is `[window_blocks | summary_blocks]` entries wide.
Nothing is handed out at admission: `reserve()` books the request's
bounded footprint (`blocks_needed`: at most a window of exact blocks
plus one summary row per `block_size` tokens), `extend()` takes blocks
from the free list as positions are about to be written, and
`close_window()` gives a full window's exact blocks back mid-request
while the summary rows stay.  Booked-but-not-held blocks are `promised`
and are not free for admission, so `extend()` never fails.  The prefix
cache, session pins and quantized rows are not offered for such a cache
(the engine refuses them by name).

One row for all heads (`latent_width > 0`, the latent layer spec): a
token's cache row is not H keys and H values but one latent of
`latent_width` values that every head attends ([c_kv | k_rope],
models/deepseek_v2.py).  A layer's entry is then ONE array
`[rows, pool_width(1, latent_width)]` (576 -> 640 lanes) and not a
(K, V) pair; blocks, tables, the free list and admission are the same
— the allocator counts blocks and never looks at a row's width.  The
prefix cache, session pins, quantized rows and a mesh are not offered
for such a cache (the engine refuses them by name).

Two groups of layers (`ring_tokens > 0`, a layer spec with "grouped"
attention whose `layer_windows` name sliding layers): a layer that
attends the last `window` positions needs at most `ring = window +
prefill_chunk` rows a request, a full layer all of them, so one block id
can no longer name a slab in every layer.  Group `full` (the layers not
in `ring_layers`) is the cache described above: `num_blocks` blocks, the
free list, `alloc` at admission for the request's whole life.  Group
`window` (`ring_layers`) has arrays, a free list and a trash block of
its own: `max_requests * ring_blocks + 1` blocks, so it never runs dry
and admission need not ask it; a request's run of it, `ring_blocks`
table entries BEHIND its `table_width` full entries (one table
`[full | window]` a request, one `tables` array a decode step), is
addressed by position modulo the ring — the row of position p is row
`p % ring` of the run — and `extend` takes a run's blocks from the
group's free list as positions are first written, never more than
`ring_blocks`.  A prefill chunk writes its `prefill_chunk` rows before
its queries attend, and the chunk's oldest query still needs the
`window - 1` positions before it: hence the ring's margin of one chunk.
Which position a ring row holds is the programs' arithmetic
(serving/layers.py), not the allocator's.  `free` returns both groups'
blocks.  The prefix cache, session pins, quantized rows and a mesh are
not offered for such a cache (the engine refuses them by name); a model
with one kind of layer is one group and sees none of this.

A second row in SOME layers (`index_layers`, `index_width` > 0, beside
latent rows: a layer spec whose `layer_indexers` mark layers "full",
models/glm_moe_dsa.py): such a layer's entry is a pair — the latent
rows and, under the same block ids, an array of index keys `[rows,
pool_width(1, index_width)]`, one key a token that the layer's indexer
scores a query against to choose the rows it attends.  The layers
marked "shared" own no such rows: their entry is the one array.  One
block id names a slab in every array, so tables, the free list and
admission do not change, and `nbytes` / `bytes_per_block` count the
second array where it exists (`index_nbytes`).  What the latent cache
refuses it refuses too.

A group of layers with a state and no rows (`state_layers`, a layer
spec some of whose layers mix tokens by a state-space recurrence): such
a layer's entry of `caches` is not rows of a pool but arrays BY SLOT,
`state_shapes` says which — for a Mamba-2 layer a float32 state
`[max_requests, heads, head_dim, state]` and the convolution's last
inputs `[max_requests, taps - 1, conv_width]` at the cache's dtype, for a
gated short convolution (models/lfm2_moe.py) those inputs alone.  No
blocks, no table entries, no free list: a request's share is its slot's,
fixed whatever its length, so the pool, `num_blocks`, `bytes_per_block`
and admission count the other layers only, and with `max_batch` slots
the state is what a deployment sizes (`state_nbytes`).  `free` leaves
it as it lies — the loop runs a step ahead, and a step launched for the
slot's last tenant may still be to run; `reset_state(slot)` zeroes a
slot's entries on the device, in launch order behind whatever was
launched before it, and the engine calls it when it seats a request,
(beside them there may be layers that own NOTHING, `bare_layers` — a
layer that is its FFN alone, models/nemotron_h.py: an entry `()` that
costs no byte, so the rows are laid out for the attention layers only)
before its first prefill chunk (counted as `<state_counters>.
state_resets`: the engine names its mixers' family, `serve.ssm`,
`serve.gdn` or `serve.conv`).  The prefix cache, session pins,
quantized rows and a mesh are not offered for such a cache (the engine
refuses them by name).

Block 0 is the reserved TRASH block: the allocator never hands it out,
block tables are padded with it, and inactive decode slots write to it —
so the jitted programs need no branches for "this slot/table entry is
not real"; bogus traffic lands in (and is read from) a block whose
contents are never attended unmasked.

Counters (monitor/counters.py): `kv.blocks_in_use` is sampled by the
engine each step (bytes += in-use blocks, mean = bytes/calls, the
input.queue_depth convention); `kv.evictions` counts blocks reclaimed
from requests that did NOT finish naturally (shed / errored), i.e.
forced frees — a healthy run keeps it at zero.  The prefix cache adds
`kv.prefix_hits` (admissions that aliased cached blocks; bytes =
blocks aliased), `kv.prefix_hit_tokens` (bytes = prompt tokens whose
prefill was skipped), `kv.cow_copies` (bytes = device bytes copied),
`kv.session_pins` (bytes = blocks pinned) and `kv.prefix_evictions`
(refcount-0 cached blocks LRU-evicted to serve an allocation).  A
windowed cache adds `kv.window_closes` (calls; bytes = exact blocks
returned to the free list) and the engine `kv.summary_rows`; over two
groups the engine adds `kv.ring_wraps`.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

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


class PagedKVCache:
    """Device block pool + host allocator for one serving engine.

    `caches` is the functional state the jitted programs thread: a list
    of (k, v) per layer, each `[num_blocks * block_size, pool_width]`.
    The engine passes it into a program and stores the returned
    (donated) arrays back; this object owns the allocator book-keeping
    only.

    Owners are opaque hashable keys: the scheduler uses request rids,
    the session store uses `("session", sid)` tuples — both walk the
    same refcount/free paths.
    """

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_blocks: int, block_size: int, table_width: int,
                 dtype=jnp.float32, mesh_info=None,
                 prefix_cache: bool = True, min_match_blocks: int = 1,
                 prefix_salt: str = "", window_tokens: int = 0,
                 latent_width: int = 0, ring_tokens: int = 0,
                 ring_layers: Sequence[int] = (), max_requests: int = 0,
                 state_layers: Sequence[int] = (),
                 state_shapes: Sequence[tuple] = (),
                 state_counters: str = "serve.ssm",
                 index_layers: Sequence[int] = (), index_width: int = 0,
                 bare_layers: Sequence[int] = ()):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved trash "
                f"block), got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if table_width < 1:
            raise ValueError(f"table_width must be >= 1, got {table_width}")
        if int(min_match_blocks) < 1:
            raise ValueError(
                f"min_match_blocks must be >= 1, got {min_match_blocks}")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.table_width = int(table_width)
        # 0: every token keeps its exact row for the request's life.
        # > 0: exact rows for the open window only; the table's first
        # `window_blocks` entries are the window, the rest summary blocks
        self.window_tokens = int(window_tokens)
        if self.window_tokens % self.block_size:
            raise ValueError(
                f"window_tokens {window_tokens} must be a multiple of "
                f"block_size {block_size}")
        self.window_blocks = self.window_tokens // self.block_size
        if self.windowed and (prefix_cache
                              or self.window_blocks >= self.table_width):
            raise ValueError(
                "a windowed cache takes no prefix cache and needs table "
                "entries for its summary blocks beyond the window's "
                f"{self.window_blocks}")
        # > 0: one row of so many values a token for all heads, and a
        # layer's entry one array, not a (K, V) pair
        self.latent_width = int(latent_width)
        self.dtype = dtype
        mode, dense_dtype = resolve_kv_dtype(dtype)
        if self.latent_width and (mode != "dense" or prefix_cache
                                  or self.windowed or mesh_info is not None
                                  and mesh_info.size > 1):
            raise ValueError(
                "a cache of latent rows is dense, on one device, with no "
                "prefix cache and no window")
        # the layers `index_layers` keep a second row a token beside the
        # latent one: an index key of `index_width` values
        self.index_layers = frozenset(int(i) for i in index_layers)
        self.index_width = int(index_width)
        if bool(self.index_layers) != bool(self.index_width) or (
                self.index_layers and not self.latent_width):
            raise ValueError(
                f"index keys of {index_width} values are kept beside "
                f"latent rows, in some layers ({sorted(self.index_layers)})")
        # > 0: the layers `ring_layers` are group `window`: rows, free
        # list and trash block of their own, `ring_blocks` table entries
        # a request behind its `table_width` full ones
        self.ring_tokens = int(ring_tokens)
        self.ring_layers = frozenset(int(i) for i in ring_layers)
        if self.ring_tokens % self.block_size or \
                bool(self.ring_tokens) != bool(self.ring_layers) or \
                (self.ring_tokens and int(max_requests) < 1):
            raise ValueError(
                f"a ring of {ring_tokens} rows must be whole blocks of "
                f"{block_size}, for some layers ({sorted(self.ring_layers)}) "
                f"and max_requests >= 1 requests ({max_requests})")
        self.ring_blocks = self.ring_tokens // self.block_size
        self.ring_pool_blocks = int(max_requests) * self.ring_blocks + 1
        if self.ring_blocks and (
                mode != "dense" or prefix_cache or self.windowed
                or self.latent_width
                or mesh_info is not None and mesh_info.size > 1):
            raise ValueError(
                "a cache of two groups of layers is dense, on one device, "
                "with no prefix cache and one kind of row")
        # the layers `state_layers` hold no rows: arrays by slot, of
        # `state_shapes` ((shape a slot, dtype or None: the cache's), ...)
        self.state_layers = frozenset(int(i) for i in state_layers)
        self._state_order = tuple(sorted(self.state_layers))
        self.state_shapes = tuple(state_shapes)
        # the layers `bare_layers` neither attend nor keep a state (a
        # layer that is its FFN alone): an entry of nothing
        self.bare_layers = frozenset(int(i) for i in bare_layers)
        if self.bare_layers & self.state_layers or (
                self.bare_layers and not self.state_layers):
            raise ValueError(
                f"layers that own nothing ({sorted(self.bare_layers)}) "
                f"stand beside layers with a state, and are none of them "
                f"({sorted(self.state_layers)})")
        # the mixers' own family of counters: serve.ssm | serve.gdn |
        # serve.conv
        self.reset_counter = f"{state_counters}.state_resets"
        self.max_requests = int(max_requests)
        if bool(self.state_layers) != bool(self.state_shapes) or (
                self.state_layers and self.max_requests < 1):
            raise ValueError(
                f"layers with a state ({sorted(self.state_layers)}) need "
                f"the shapes of what a slot keeps ({self.state_shapes}) "
                f"and max_requests >= 1 slots ({max_requests})")
        if self.state_layers and (
                mode != "dense" or prefix_cache or self.windowed
                or self.latent_width or self.ring_blocks
                or mesh_info is not None and mesh_info.size > 1):
            raise ValueError(
                "a cache with a group of layers that keep a state a slot "
                "is dense, on one device, with no prefix cache, one kind "
                "of row and no ring")
        self._reset_fn = None                     # lazy jitted slot zeroing
        # "int8"/"int4" when blocks are stored quantized, else None
        self.quant_wire = mode if mode in KV_QUANT_WIRES else None
        self.dense_dtype = dense_dtype
        if self.quant_wire == "int4" and self.head_dim % 2:
            raise ValueError(
                f"int4 KV packs two codes per byte and needs an even "
                f"head_dim, got {self.head_dim}")
        self._sharding = self._kv_sharding(mesh_info)
        self._scale_sharding = self._scale_kv_sharding(mesh_info)
        self.caches = self._init_caches()
        # block 0 reserved as trash; LIFO free list so the fragmentation
        # tests exercise immediate reuse of just-freed blocks
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._owned: Dict[Any, List[int]] = {}
        # holders per block (live requests + session pins); absent = 0
        self._ref: Dict[int, int] = {}
        # windowed owners: rid -> [table, booked blocks, closed windows]
        self._booked: Dict[Any, list] = {}
        # group `window`: its free list (block 0 its trash block) and
        # rid -> the request's table [full | window], rewritten in place
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

    def _kv_sharding(self, mesh_info):
        """Heads sharded over the TP `model` axis when a mesh is in
        scope and divides them; None otherwise (plain local arrays).
        For the flat pool that is a split of the row's columns, which
        falls on head boundaries when the row has no padding lanes (a
        padded row still shards, and the partitioner moves the lanes
        that land on another rank than their head's)."""
        if mesh_info is None:
            return None
        from ..comm.mesh import MODEL_AXIS

        tp = mesh_info.axis_size(MODEL_AXIS)
        if tp <= 1:
            return None
        if self.num_heads % tp:
            from ..utils.logging import logger

            logger.warning(
                f"serving KV cache: model axis {tp} does not divide "
                f"num_heads {self.num_heads}; cache stays unsharded")
            return None
        return mesh_info.sharding(None, MODEL_AXIS)

    def _scale_kv_sharding(self, mesh_info):
        """Scales are [rows, H] — same head split as the payload."""
        if self._sharding is None:
            return None
        from ..comm.mesh import MODEL_AXIS

        return mesh_info.sharding(None, MODEL_AXIS)

    def _init_caches(self):
        rows = self.num_blocks * self.block_size
        if self.state_layers:
            shape = (rows, pool_width(self.num_heads, self.head_dim))

            def entry(i):
                if i in self.bare_layers:
                    return ()
                if i not in self.state_layers:
                    return (jnp.zeros(shape, self.dense_dtype),
                            jnp.zeros(shape, self.dense_dtype))
                return tuple(
                    jnp.zeros((self.max_requests,) + tuple(a_slot),
                              dtype or self.dense_dtype)
                    for a_slot, dtype in self.state_shapes)

            return [entry(i) for i in range(self.num_layers)]
        if self.latent_width:
            shape = (rows, pool_width(1, self.latent_width))
            keys = (rows, pool_width(1, self.index_width))
            return [(jnp.zeros(shape, self.dense_dtype),) + (
                (jnp.zeros(keys, self.dense_dtype),)
                if i in self.index_layers else ())
                for i in range(self.num_layers)]
        if self.ring_blocks:
            width = pool_width(self.num_heads, self.head_dim)
            ring_rows = self.ring_pool_blocks * self.block_size

            def pair(i):
                shape = (ring_rows if i in self.ring_layers else rows, width)
                return (jnp.zeros(shape, self.dense_dtype),
                        jnp.zeros(shape, self.dense_dtype))

            return [pair(i) for i in range(self.num_layers)]
        if self.quant_wire is None:
            shape = (rows, pool_width(self.num_heads, self.head_dim))

            def mk():
                z = jnp.zeros(shape, self.dense_dtype)
                return (z if self._sharding is None
                        else jax.device_put(z, self._sharding))
        else:
            width = (self.head_dim if self.quant_wire == "int8"
                     else self.head_dim // 2)
            pdt = jnp.int8 if self.quant_wire == "int8" else jnp.uint8

            def mk():
                # zero payload + zero scale dequantizes to exact zero,
                # matching the dense cache's zero init
                payload = jnp.zeros(
                    (rows, pool_width(self.num_heads, width)), pdt)
                scales = jnp.zeros((rows, self.num_heads), jnp.float16)
                if self._sharding is not None:
                    payload = jax.device_put(payload, self._sharding)
                    scales = jax.device_put(scales, self._scale_sharding)
                return (payload, scales)

        return [(mk(), mk()) for _ in range(self.num_layers)]

    def nbytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(self.caches))

    def state_nbytes(self) -> int:
        """Device bytes of the layers that keep a state a slot: all
        slots', whatever is seated."""
        return sum(int(a.size) * a.dtype.itemsize
                   for i in self.state_layers for a in self.caches[i])

    def index_nbytes(self) -> int:
        """Device bytes of the index keys: the second array of the
        layers that select the rows their queries attend."""
        return sum(int(self.caches[i][1].size)
                   * self.caches[i][1].dtype.itemsize
                   for i in self.index_layers)

    def bytes_per_block(self) -> int:
        """Device bytes one block costs across all layers with rows (K
        and V)."""
        return (self.nbytes() - self.state_nbytes()) // self.num_blocks

    def reset_state(self, slot: int) -> None:
        """Zero slot `slot`'s entries of every layer with a state, on
        the device and in place: behind every program already launched,
        before any launched after; counted under `reset_counter`."""
        if self._reset_fn is None:
            self._reset_fn = jax.jit(
                lambda states, slot: jax.tree_util.tree_map(
                    lambda a: a.at[slot].set(0), states),
                donate_argnums=(0,))
        states = self._reset_fn([self.caches[i] for i in self._state_order],
                                np.int32(slot))
        for i, entry in zip(self._state_order, states):
            self.caches[i] = entry
        COUNTERS.add(self.reset_counter)

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
        what windowed requests have booked and not yet taken."""
        return len(self._free) + len(self._lru) - self.promised_blocks

    @property
    def windowed(self) -> bool:
        return self.window_tokens > 0

    @property
    def promised_blocks(self) -> int:
        """Blocks booked by `reserve` that their owners do not hold
        right now (not yet written, or given back at a window close)."""
        return sum(b[1] - len(self._owned[rid])
                   for rid, b in self._booked.items())

    @property
    def token_capacity(self) -> int:
        """The longest request one table can address."""
        if not self.windowed:
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
        """The most blocks a request of `n_tokens` holds at once: every
        token's exact block, or, windowed, at most a window of exact
        blocks plus the blocks of one summary row per `block_size`
        tokens."""
        exact = -(-int(n_tokens) // self.block_size)
        if not self.windowed:
            return exact
        summary_rows = int(n_tokens) // self.block_size
        return min(exact, self.window_blocks) + \
            -(-summary_rows // self.block_size)

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
        block is copied to a private block first (copy-on-write)."""
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

    # -- two kinds of row: windowed requests ---------------------------

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

    def _take_into(self, rid, table, entry: int) -> None:
        if table[entry] == TRASH_BLOCK:
            b = self._take_free()
            self._ref[b] = 1
            self._owned[rid].append(b)
            table[entry] = b

    def extend(self, rid, start: int, stop: int) -> np.ndarray:
        """Before positions [start, stop) are written: take the exact
        blocks of those window offsets and the summary blocks of the
        chunks they complete.  Never fails: the blocks were booked.

        -> a copy of the table as it now stands.  The booked table is
        rewritten in place here and in `close_window`, and a program
        that was handed it runs after its caller has returned (on the
        CPU `jnp.asarray` may alias a host array, not copy it): a
        prefill chunk that filled the window then read the table after
        `close_window` had trashed it.

        Over two groups of layers: take the blocks of group `window`
        that hold rows `p % ring` of those positions, where the
        request's run does not have them yet; group `full` was handed
        out whole at admission."""
        if self.ring_blocks:
            table = self._ring[rid]
            bs, rb = self.block_size, self.ring_blocks
            last = min(-(-int(stop) // bs), int(start) // bs + rb)
            for blk in range(int(start) // bs, last):
                entry = self.table_width + blk % rb
                if table[entry] == TRASH_BLOCK:
                    table[entry] = self._ring_free.pop()
            return table.copy()
        table = self._booked[rid][0]
        bs, wb = self.block_size, self.window_blocks
        for blk in range(int(start) // bs, -(-int(stop) // bs)):
            self._take_into(rid, table, blk % wb)
        for chunk in range(int(start) // bs, int(stop) // bs):
            self._take_into(rid, table, wb + chunk // bs)
        return table.copy()

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

    def ring_blocks_of(self, rid) -> List[int]:
        """The blocks of group `window` the request holds."""
        table = self._ring.get(rid)
        if table is None:
            return []
        return [int(b) for b in table[self.table_width:]
                if b != TRASH_BLOCK]

    @property
    def ring_blocks_in_use(self) -> int:
        """Blocks of group `window` with a holder."""
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
        rows = "exact rows" if not self.windowed else (
            f"exact rows for a window of {self.window_tokens} tok "
            f"({self.window_blocks} blocks) + summary rows 1 per "
            f"{self.block_size} tok "
            f"({self.table_width - self.window_blocks} blocks)")
        if self.ring_blocks:
            rows += (f" in {self.num_layers - len(self.ring_layers)} full "
                     f"layer(s); {len(self.ring_layers)} layer(s) with a "
                     f"window in a ring of {self.ring_tokens} rows a "
                     f"request ({self.ring_pool_blocks} blocks of their "
                     f"own)")
        if self.state_layers:
            with_rows = self.num_layers - len(self.state_layers) \
                - len(self.bare_layers)
            rows += (f" in {with_rows} "
                     f"layer(s); {len(self.state_layers)} layer(s) with no "
                     f"rows and a state a slot, {self.max_requests} slots "
                     f"({self.state_nbytes() / (1 << 20):.2f} MiB)")
            if self.bare_layers:
                rows += (f"; {len(self.bare_layers)} layer(s) with neither")
        return (f"PagedKVCache(layers={self.num_layers}, "
                f"blocks={self.num_blocks} x {self.block_size} rows, {rows}, "
                f"table_width={self.table_width}, " + (
                    f"one latent row of {self.latent_width}, "
                    if self.latent_width else
                    f"heads={self.num_heads}, head_dim={self.head_dim}, ")
                + (f"index keys of {self.index_width} in layers "
                   f"{sorted(self.index_layers)} "
                   f"({self.index_nbytes() / (1 << 20):.2f} MiB), "
                   if self.index_layers else "")
                + f"kv={mode}, "
                f"prefix_cache={'on' if self.prefix_enabled else 'off'}, "
                f"sharded={self._sharding is not None}, "
                f"{self.nbytes() / (1 << 20):.2f} MiB)")
