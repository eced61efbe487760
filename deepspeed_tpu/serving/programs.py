"""Compiled serving programs: ONE jitted prefill and ONE jitted decode,
built through StepBuilder-style schedule composition, for any model that
hands over a layer spec.

Embed, block and head come from the model: `model.layer_spec()` says
which norm, positions, attention and FFN a block is made of
(`models/layer_spec.py`), and `serving/layers.py` assembles them.  This
module owns what is common to every family — the schedule, the three
program shapes, sampling, the qwZ weight store — and names no model.

Like runtime/step_builder.py collapsed the three training step paths
into one composition engine, serving lowers its two phases into two
fixed-shape programs built once from a declarative `ServeSchedule`
(`describe()` logged at build, the schedule-log contract):

* **prefill** — one prompt CHUNK of static length `prefill_chunk` for
  one request: embeds, writes the chunk's K/V through the block table,
  attends causally against everything cached so far, and (for the final
  chunk) samples the request's FIRST token in-program.  Chunking is what
  keeps a long prompt from stalling the decode batch: the scheduler
  interleaves one chunk per engine step with full decode steps.
* **decode** — one token for every slot of the packed batch
  `[max_batch]`: per-slot block-table write + paged attention through
  the table (on a TPU a walk of the slot's live blocks in the pool,
  kernels/paged.py; elsewhere a gather of the table's rows) + per-slot
  sampling.  Every operation is row-wise (layernorm, per-slot attention,
  per-row matmul dots, per-row RNG), which is the batching-invariance
  contract tier-1 pins: a
  request's tokens do not depend on WHICH other requests share the
  batch, so joining mid-flight is token-identical to decoding alone.
  Beside its tokens and caches it returns what the NEXT step takes as
  `tokens` and `positions` (the slots' samples, the positions advanced
  for the active slots), so the engine launches that step from arrays
  that never leave the device; **seat** writes a prompt's first token
  (prefill's sample) into that vector at the request's slot.
* **verify** — the speculative-decoding forward: decode at
  `draft_len + 1` tokens per slot, scoring a slot's drafted candidates
  in ONE dispatch.  Position-keyed sampling at every row makes the
  accepted prefix bit-identical to sequential decode — the engine's
  accept/reject loop (`engine._verify_step`) rides this.

KV storage (`ServeSchedule.kv_dtype`): "dense" keeps K/V rows at the
cache arrays' dtype (param dtype or an explicit bf16 cache);
"int8"/"int4" store (payload, per-(row, head) fp16 scale) pairs via
runtime/comm/quant.py's row kernels and dequantize gathered rows to
fp32 in-program.  The surrounding attention math is shared, so parity
contracts hold at matched kv_dtype.

A spec with "latent" attention (one row a token for all heads) runs the
same two programs over the paged table; its block picks the expanded or
the absorbed products from the call's query count (serving/layers.py).
Behind a routed-experts FFN the decode program appends to its tokens how
many experts the step touched.  `verify`, quantized weights and
quantized rows are not built for it.  Where the spec's `layer_indexers`
mark layers that choose the rows a query attends, `layers.blocks` hands
each such layer's selection to the layers behind it inside the one
program (serving/sparse.py); nothing else here knows of it.

A spec with "grouped" attention (rows of `kv_heads` keys and values,
layers with a window beside full ones) runs the same two programs; where
the cache has two groups of layers (`ring_blocks > 0`) a request's table
is `[table_width full entries | ring_blocks entries of the window
group]` and the sliding layers' blocks address their ring by position
(serving/layers.py).  Behind a share of the experts the number decode
appends to its tokens counts the experts touched among those held.
`verify`, quantized weights and quantized rows are not built for it.

A spec some of whose layers mix tokens by a state-space recurrence or
by the gated delta rule (`layer_mixers`; grouped attention in the
others) runs the same two programs: such a layer's entry of `caches` is its
state and its convolution's last inputs BY SLOT, donated and written in
place with the rows; `prefill` is told its request's slot by one more
entry behind the table's, `decode` steps every slot's state and leaves a
slot that is not running as it found it (serving/layers.py).  Zeroing a
slot for a new request is the cache's (`PagedKVCache.reset_state`), not
a program built here.  `verify`, quantized weights and quantized rows
are not built for it.

A spec with "eva" attention (exact rows for an open window, summary
rows behind it) runs the same two programs over a table of
`[window blocks | summary blocks]`; the block also writes the summary
of every chunk a call completes, so closing a window needs no program
of its own — it is host book-keeping in the engine.  Its `verify`
program is not built (`draft_len` must be 0), nor are quantized weights
or quantized rows for it.

Stages: every operation of the four programs is written under exactly
one of six `jax.named_scope`s, the same in every family — `embed` (the
weights' dequantisation, the embedding, the call's addressing), `attn`
or `state` and `ffn` (a block's two halves, serving/layers.py `block`),
`head` (final norm and logits) and `sample` (the sampling tail and
whatever a program appends to its tokens; `seat`) — with the layers' own
scopes (`full_attend`, `moe_experts`, `kernel.<op>` ...) beneath them.  A
scope is metadata on the instructions and nothing else; `ServeEngine.
attach_tracing` reads it back from the compiled programs
(`monitor/tracing.py::program_scopes`) so that a reader of the device
trace can name each instruction's stage.

The paged attention math deliberately mirrors models/generation.py
`_block_with_cache` op for op (serving/layers.py) so greedy serving
output of a GPT is bit-identical to `generate()` when the cache lengths
agree — pinned in tests/test_serving.py.

Sampling determinism: the key for the token generated at absolute
position p is `fold_in(PRNGKey(request.seed), p)` — a pure function of
the request, never of the batch composition or the step count, so
sampled output is identical-under-seed across batch join/leave too.
What a call's sampling tail computes follows from what its live rows
ask for (`sample_rows`): the argmax alone where none has a temperature
above 0 — the keys, the scaled logits and the noise over [rows, V]
stand inside a conditional — and the k-th largest value, selected and
never sorted for, only where a sampling row sets `top_k`.  A row's
token is the same on every path it can take.

qwZ weights (`quantized="int8"|"int4"`): weights are stored blockwise
quantized (runtime/comm/quant.py, the PR-7 kernels) and dequantized at
program entry — KV/weight memory headroom at rest at the cost of a
transient full-precision copy during the step (the ZeRO++ qwZ trade,
see docs/tutorials/serving.md).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..models.generation import kth_largest
from ..utils.logging import logger
from . import layers
from .kv_cache import ring_blocks_for

QUANT_MODES = ("none", "int8", "int4")

# the top-level scopes every operation of a program lies under (the
# module's docstring, "Stages"; serving/layers.py `block` writes three)
STAGES = ("embed", "attn", "state", "ffn", "head", "sample")

# how the cache stores K/V: "dense" = at the cache arrays' own dtype
# (the dtype is a runtime property of the arrays, not program
# structure), "int8"/"int4" = (payload, scales) rows quantized through
# runtime/comm/quant.py and dequantized in-program at every gather
KV_MODES = ("dense", "int8", "int4")


class ServeSchedule(NamedTuple):
    """Declarative description of the serving program pair (the
    StepSchedule analogue; `describe()` is logged at build time)."""

    max_batch: int
    prefill_chunk: int
    block_size: int
    num_blocks: int
    table_width: int
    quantized: str = "none"        # "none" | "int8" | "int4"
    quant_block: int = 256
    kv_dtype: str = "dense"        # "dense" | "int8" | "int4"
    draft_len: int = 0             # speculative candidates per verify
    window_blocks: int = 0         # > 0: the table's first entries are
    #                                the open window's exact blocks, the
    #                                rest summary blocks (one row a block
    #                                of tokens)
    ring_blocks: int = 0           # > 0: behind the table's `table_width`
    #                                entries, so many of the window
    #                                group's: a ring of rows a request

    def describe(self) -> str:
        if self.window_blocks:
            summary = self.table_width - self.window_blocks
            cap = summary * self.block_size ** 2
            rows = (f"exact rows for a window of {self.window_blocks} "
                    f"blocks + {summary} blocks of summary rows, 1 per "
                    f"{self.block_size} tok")
        else:
            cap = self.table_width * self.block_size
            rows = "exact rows"
            if self.ring_blocks:
                rows += (f", layers with a window in a ring of "
                         f"{self.ring_blocks} blocks a request")
        q = "" if self.quantized == "none" else f", qwZ={self.quantized}"
        kv = "" if self.kv_dtype == "dense" else f", kv={self.kv_dtype}"
        spec = "" if not self.draft_len else \
            f", spec draft {self.draft_len}"
        return (f"serve schedule: decode[{self.max_batch} slots] + "
                f"prefill[chunk {self.prefill_chunk}], paged KV "
                f"{self.num_blocks} x {self.block_size} rows, {rows} "
                f"(per-request cap {cap}){q}{kv}{spec}")

    def program_key(self):
        """The fields the COMPILED programs actually depend on.
        `num_blocks` is not one of them: the cache arrays are runtime
        inputs, a different pool size just retraces — so engines with
        different pool sizes can share one program pair."""
        return self._replace(num_blocks=0)


# -- sampling ---------------------------------------------------------------


def _row_key(seed, position):
    """THE sampling-key rule: the token generated at absolute position
    p uses fold_in(PRNGKey(seed), p) — shared by prefill (first token)
    and decode so batch composition can never reach the RNG stream."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), position)


def top_k_filter(scaled, top_ks):
    """`scaled` [N, V] float32 with everything under a row's k-th
    largest value at -inf (ties at the threshold survive, the HF
    semantics generation.py documents); `top_ks` [N] <= 0 leaves a row
    as it is, >= V keeps everything."""
    k = jnp.clip(top_ks, 1, scaled.shape[-1])
    kth = kth_largest(scaled, k)[:, None]
    return jnp.where((top_ks > 0)[:, None] & (scaled < kth), -jnp.inf,
                     scaled)


def sample_rows(logits, temperatures, top_ks, live, keys):
    """One token a row of `logits` [N, V]: greedy at temperature 0,
    else temperature + optional top-k truncation, drawn with the row's
    key.  `temperatures` / `top_ks` [N] are per-request ARRAYS (not
    static), so one compiled program serves every request mix — and the
    tail does only the work the CALL's rows ask for: where no `live`
    row samples it is the argmax alone (`lax.cond` over the whole call:
    no scaled copy, no keys, no noise over [N, V]); where one samples
    and no live sampling row sets `top_k`, the draw without the
    threshold; the threshold only where one does.  `live` [N] marks the
    rows whose token anybody reads — a freed slot keeps the temperature
    of the request that left it.  `keys` is called inside the sampling
    branch and returns the rows' keys [N].  A greedy row is the same
    argmax on every path, so its token does not depend on what its
    neighbours asked for; neither does a sampled live row's: a
    threshold skipped is one that no live sampling row set."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    samples = live & (temperatures > 0)

    def draw():
        t = jnp.where(temperatures > 0, temperatures, 1.0)
        scaled = logits.astype(jnp.float32) / t[:, None]
        filtered = jax.lax.cond(
            jnp.any(samples & (top_ks > 0)),
            lambda s: top_k_filter(s, top_ks), lambda s: s, scaled)
        drawn = jax.vmap(partial(jax.random.categorical, axis=-1))(
            keys(), filtered)
        return jnp.where(temperatures > 0, drawn.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(samples), draw, lambda: greedy)


def sample_token(logits, temperature, top_k, key):
    """One row of `sample_rows`, with the caller's key."""
    return sample_rows(
        logits[None], jnp.asarray(temperature)[None],
        jnp.asarray(top_k)[None], jnp.ones((1,), bool),
        lambda: key[None])[0]


@jax.jit
def seat(tokens, slot, tok):
    """A request joins the decode batch: its first token, sampled by
    `prefill` and still on the device, becomes its slot's entry of the
    vector the next `decode` takes as `tokens`."""
    with jax.named_scope("sample"):
        return tokens.at[slot].set(tok)


@jax.jit
def seat_counted(tokens, slot, tok):
    """`seat` behind routed FFNs, where `prefill`'s sample is the first
    of two entries."""
    with jax.named_scope("sample"):
        return tokens.at[slot].set(tok[0])


# -- qwZ weight store -------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class QuantLeaf:
    """One blockwise-quantized weight: (payload, scales) ride the tree
    as array children, (shape, dtype) as static aux data — so a
    quantized params tree is a normal jit argument."""

    def __init__(self, payload, scales, shape, dtype):
        self.payload = payload
        self.scales = scales
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)

    def tree_flatten(self):
        return (self.payload, self.scales), (self.shape, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])


def quantize_params(params, wire: str, block: int):
    """Blockwise-quantize every matmul-sized leaf (ndim >= 2) of a
    params tree into a `QuantLeaf`; small vectors (biases, layernorm
    scales) stay exact."""
    from ..runtime.comm.quant import quantize_blockwise

    def q(leaf):
        if getattr(leaf, "ndim", 0) < 2:
            return leaf
        payload, scales = quantize_blockwise(leaf, block, wire)
        return QuantLeaf(payload, scales, leaf.shape, leaf.dtype)

    return jax.tree_util.tree_map(
        q, params, is_leaf=lambda x: hasattr(x, "ndim"))


def dequantize_params(qparams, wire: str, block: int):
    """Inverse of quantize_params, usable under jit (program entry)."""
    from ..runtime.comm.quant import dequantize_blockwise

    def dq(node):
        if isinstance(node, QuantLeaf):
            n = 1
            for s in node.shape:
                n *= int(s)
            flat = dequantize_blockwise(node.payload, node.scales, wire, n)
            return flat.reshape(node.shape).astype(node.dtype)
        return node

    return jax.tree_util.tree_map(
        dq, qparams, is_leaf=lambda x: isinstance(x, QuantLeaf))


# -- builder ----------------------------------------------------------------


class ServeProgramBuilder:
    """Builds the jitted {prefill, decode} pair for one (model,
    schedule).  Programs are pure: (params, caches, batch state) ->
    (outputs, caches), caches donated — the engine threads the
    returned arrays back through PagedKVCache.caches.  Nothing else is
    donated: a slot-state array is handed to step after step."""

    def __init__(self, model, schedule: ServeSchedule):
        cfg = model.config
        self.spec = layers.check_spec(model.layer_spec())
        if schedule.quantized not in QUANT_MODES:
            raise ValueError(
                f"serving quantized_weights must be one of {QUANT_MODES}, "
                f"got {schedule.quantized!r}")
        if schedule.kv_dtype not in KV_MODES:
            raise ValueError(
                f"serving schedule kv_dtype must be one of {KV_MODES}, "
                f"got {schedule.kv_dtype!r}")
        if schedule.kv_dtype == "int4" and cfg.head_dim % 2:
            raise ValueError(
                f"int4 KV packs two codes per byte and needs an even "
                f"head_dim, got {cfg.head_dim}")
        if int(schedule.draft_len) < 0:
            raise ValueError(
                f"serving draft_len must be >= 0, got "
                f"{schedule.draft_len}")
        if self.spec.attention == "eva":
            self._check_eva(schedule)
        if self.spec.attention == "latent":
            self._check_latent(schedule)
        if self.spec.has_state:
            self._check_state(schedule)
        if self.spec.attention == "grouped":
            self._check_grouped(schedule)
        self.model = model
        self.schedule = schedule

    def _check_eva(self, s: ServeSchedule) -> None:
        """What the summarised-window programs need of a schedule, and
        what is not built for them."""
        spec = self.spec
        if s.block_size != spec.chunk:
            raise ValueError(
                f"block_size must equal the model's chunk_size "
                f"({spec.chunk}) — one exact block is one chunk, "
                f"{spec.chunk} summary rows one summary block — got "
                f"{s.block_size}")
        if spec.window % s.prefill_chunk or s.prefill_chunk % spec.chunk:
            raise ValueError(
                f"prefill_chunk must divide the model's window_size "
                f"({spec.window}) and hold whole chunks of {spec.chunk} "
                f"(a prefill chunk then lies inside one window), got "
                f"{s.prefill_chunk}")
        if s.window_blocks != spec.window // spec.chunk:
            raise ValueError(
                f"schedule.window_blocks must be window_size / chunk_size "
                f"= {spec.window // spec.chunk}, got {s.window_blocks}")
        if s.draft_len:
            raise NotImplementedError(
                "draft_len > 0 over summarised windows: the verify "
                "program is not built (a rejected draft may have "
                "completed a chunk, and its summary row would have to be "
                "rewound with it)")
        if s.quantized != "none":
            raise NotImplementedError(
                "quantized_weights over summarised windows: the qwZ "
                "store is written for the GPT parameter tree's matmul "
                "leaves and is not proven on this family's")
        if s.kv_dtype != "dense":
            raise NotImplementedError(
                f"kv_dtype {s.kv_dtype!r} over summarised windows: "
                f"summary rows are pooled in float32 from the stored "
                f"rows and have no quantized codec yet")

    def _check_grouped(self, s: ServeSchedule) -> None:
        """What the programs over grouped rows need of a schedule, and
        what is not built for them."""
        # (the plan's own arithmetic: a schedule built by hand is held to it)
        window = max(self.spec.layer_windows, default=0)
        ring = s.ring_blocks * s.block_size
        if s.ring_blocks and (not window or s.ring_blocks < ring_blocks_for(
                window, s.prefill_chunk, s.block_size)):
            raise ValueError(
                f"a ring of {ring} rows needs layers with a window and "
                f"must hold it ({window}) and one prefill chunk "
                f"({s.prefill_chunk}): the chunk's rows are written "
                f"before its oldest query has attended the window's")
        if s.draft_len:
            raise NotImplementedError(
                "draft_len > 0 over grouped rows: a rejected draft's rows "
                "would have overwritten the oldest rows of a ring, which "
                "the rewound query still attends; the verify program is "
                "not built for them")
        if s.quantized != "none":
            raise NotImplementedError(
                "quantized_weights over grouped rows: the qwZ store is "
                "written for the GPT parameter tree's matmul leaves and "
                "is not proven on stacked experts")
        if s.kv_dtype != "dense":
            raise NotImplementedError(
                f"kv_dtype {s.kv_dtype!r} over grouped rows: the row "
                f"codecs are read by the paged gather, and the grouped "
                f"gather over a ring is not built for their scales")

    def _check_state(self, s: ServeSchedule) -> None:
        """What the programs over layers with a state need of a
        schedule, and what is not built for them."""
        if s.draft_len:
            raise NotImplementedError(
                "draft_len > 0 over layers with a state: a rejected draft "
                "has moved the state on, and nothing keeps the state it "
                "would have to be rewound to; the verify program is not "
                "built for them")
        if s.quantized != "none":
            raise NotImplementedError(
                "quantized_weights over layers with a state: the qwZ store "
                "is written for the GPT parameter tree's matmul leaves and "
                "would quantize the convolution's taps with them; not "
                "proven, so not offered")
        if s.kv_dtype != "dense":
            raise NotImplementedError(
                f"kv_dtype {s.kv_dtype!r} over layers with a state: the "
                f"row codecs are read by the paged gather, and a state "
                f"kept in fewer bits accumulates its rounding at every "
                f"token; neither is built")
        chunk = self.spec.state_chunk       # 0: the kind has no scan
        if chunk and s.prefill_chunk > chunk and s.prefill_chunk % chunk:
            raise ValueError(
                f"prefill_chunk must be at most the model's scan chunk "
                f"({chunk}) or whole chunks of it (the scan takes a prefill "
                f"chunk {chunk} positions at a time), got {s.prefill_chunk}")

    @staticmethod
    def _check_latent(s: ServeSchedule) -> None:
        """What is not built over latent rows."""
        if s.draft_len:
            raise NotImplementedError(
                "draft_len > 0 over latent rows: the verify program "
                "scores a few queries a slot, between the shapes the "
                "expanded and the absorbed path were measured at, and is "
                "not proven against the reference")
        if s.quantized != "none":
            raise NotImplementedError(
                "quantized_weights over latent rows: the qwZ store is "
                "written for the GPT parameter tree's matmul leaves and "
                "is not proven on stacked experts or on W_kv_b, whose "
                "two halves the decode path multiplies separately")
        if s.kv_dtype != "dense":
            raise NotImplementedError(
                f"kv_dtype {s.kv_dtype!r} over latent rows: the row "
                f"codecs scale per (row, head), and a latent row has no "
                f"heads — its norm-ed latent and its rotary key would "
                f"need scales of their own")

    def build(self) -> dict:
        logger.info(self.schedule.describe())
        return {"schedule": self.schedule,
                "prefill": self._build_prefill(),
                "decode": self._build_decode(),
                "seat": seat_counted if self._routed() else seat,
                "verify": self._build_verify(),
                "prepare_params": self._prepare_params}

    def _routed(self) -> bool:
        """Whether some layer has a routed FFN: `prefill` and `decode`
        then return their counts behind their tokens."""
        return bool(self.spec.routed_layers(self.model.config.num_layers))

    def _prepare_params(self, params):
        """Engine-side one-time weight prep for the schedule's quant
        mode (identity for "none")."""
        s = self.schedule
        if s.quantized == "none":
            return params
        # eager one-time prep (the tree carries shape/dtype metadata
        # beside the arrays, so it is not a jittable return value)
        qp = quantize_params(params, wire=s.quantized, block=s.quant_block)
        logger.info(f"serving qwZ weights: matmul leaves stored "
                    f"{s.quantized} blockwise (block {s.quant_block}), "
                    f"dequantized at program entry")
        return qp

    def _maybe_dequant(self, params):
        s = self.schedule
        if s.quantized == "none":
            return params
        return dequantize_params(params, s.quantized, s.quant_block)

    def _build_prefill(self):
        cfg, spec = self.model.config, self.spec
        s = self.schedule
        C = s.prefill_chunk

        @partial(jax.jit, donate_argnums=(1,))
        def prefill(params, caches, tokens, pos, n_valid, table,
                    temperature, top_k, seed):
            """tokens [1, C] (zero-padded past n_valid) at absolute
            position `pos`; writes the chunk's K/V through `table`
            [W] and returns (first-token sample, last-valid-row
            logits, caches).  The sample is only meaningful on the
            FINAL chunk (the engine ignores it otherwise).  Behind
            routed FFNs the sample is followed by one more entry, the
            assignment rows the chunk's routed products multiplied
            summed over those layers (int32 [2]): it rides the one
            transfer the engine makes a request."""
            with jax.named_scope("embed"):
                params = self._maybe_dequant(params)
                abs_pos = pos + jnp.arange(C)
                x = layers.embed_chunk(spec, params, tokens, abs_pos)
                addr = layers.address_chunk(spec, s, table, pos, abs_pos,
                                            n_valid)
            x, new_caches, rows = layers.blocks(spec, cfg, params, x, caches,
                                                addr, s, count="rows")
            with jax.named_scope("head"):
                x = layers.final_norm(spec, params, x)
                last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1,
                                                    axis=1)
                logits = layers.logits(spec, params, last[:, 0, :])  # [1, V]
            with jax.named_scope("sample"):
                tok = sample_rows(
                    layers.sampled(spec, logits), temperature[None],
                    top_k[None], jnp.ones((1,), bool),
                    lambda: _row_key(seed, pos + n_valid)[None])[0]
                if rows:
                    tok = jnp.stack([tok, sum(rows)])
                return tok, logits[0], new_caches

        return prefill

    def step_logits(self, params, caches, tokens, positions, active,
                    tables):
        """One decode step up to its logits: (logits [R, V], caches,
        touched).  `decode` is this and per-slot sampling; a test that
        wants the logits a token was drawn from jits this itself.
        `touched` holds, for each layer with a routed FFN, how many of
        its experts the active slots chose."""
        cfg, spec, s = self.model.config, self.spec, self.schedule
        with jax.named_scope("embed"):
            x = layers.embed_step(spec, params, tokens, positions)
            addr = layers.address_step(spec, s, tables, positions, active)
        x, new_caches, touched = layers.blocks(spec, cfg, params, x, caches,
                                               addr, s)
        with jax.named_scope("head"):
            x = layers.final_norm(spec, params, x)
            return (layers.logits(spec, params, x[:, -1, :]), new_caches,
                    touched)

    def _build_decode(self):
        spec = self.spec

        @partial(jax.jit, donate_argnums=(1,))
        def decode(params, caches, tokens, positions, active, tables,
                   temperatures, top_ks, seeds):
            """One token for every slot: tokens [R] (each slot's last
            token), positions [R] (its write position = current cached
            length), active [R] bool, tables [R, W], sampling params
            [R].  Inactive slots write to the trash block and their
            outputs are discarded by the engine — all slot math is
            row-wise, THE batching-invariance contract.  Returns (what
            the host reads, caches, what the next step takes): behind
            routed FFNs the tokens [R] the host reads are followed by
            one more entry, the experts the step touched summed over
            those layers — it rides the one transfer the engine makes a
            step; the next step's (tokens [R], positions [R]) are the
            samples as they lie and the positions moved on by one for
            the active slots."""
            with jax.named_scope("embed"):
                params = self._maybe_dequant(params)
            logits, new_caches, touched = self.step_logits(
                params, caches, tokens, positions, active, tables)
            with jax.named_scope("sample"):
                toks = sample_rows(
                    layers.sampled(spec, logits), temperatures, top_ks,
                    active, lambda: jax.vmap(_row_key)(seeds, positions + 1))
                ahead = (toks, positions + active.astype(positions.dtype))
                if touched:
                    toks = jnp.concatenate([toks, sum(touched)[None]])
                return toks, new_caches, ahead

        return decode

    def _build_verify(self):
        """The speculative batched forward: decode's math at T =
        draft_len + 1 tokens per slot instead of one.  Row i of a slot
        holds its (i-1)-th DRAFT candidate (row 0 the last committed
        token); the program writes all candidate K/V through the table,
        attends causally (row i sees rows <= i plus everything cached)
        and samples the target token at EVERY position with the same
        `_row_key(seed, position + 1)` rule decode uses — so
        `toks[r, i]` is bit-identical to what `draft_len` sequential
        decode steps would have produced given the same prefix, which
        is the whole accept/reject correctness argument.  Rejected
        rows need no undo: the engine simply rewinds its position and
        the stale rows are re-written (same scatter indices) before
        any later query's causal mask can reach them."""
        cfg, spec = self.model.config, self.spec
        s = self.schedule
        T = int(s.draft_len) + 1

        @partial(jax.jit, donate_argnums=(1,))
        def verify(params, caches, tokens, positions, n_draft, active,
                   tables, temperatures, top_ks, seeds):
            """tokens [R, T] = column 0 each slot's last committed
            token, columns 1..draft_len its drafted candidates (pad
            past n_draft[r] ignored); positions [R] = the committed
            token's write position.  Returns (target samples [R, T],
            caches): toks[r, i] is the token the target emits at
            absolute position positions[r] + 1 + i given the prefix
            through column i."""
            R = tokens.shape[0]
            with jax.named_scope("embed"):
                params = self._maybe_dequant(params)
                abs_pos = positions[:, None] + jnp.arange(T)[None, :]
                # pad rows past the position table clamp (their writes
                # land in trash and their samples are discarded by the
                # engine)
                x = layers.embed_chunk(spec, params, tokens,
                                       abs_pos)              # [R, T, D]
                addr = layers.address_grid(spec, s, tables, abs_pos, active,
                                           n_draft)
            x, new_caches, _ = layers.blocks(spec, cfg, params, x, caches,
                                             addr, s)
            with jax.named_scope("head"):
                x = layers.final_norm(spec, params, x)
                logits = layers.logits(
                    spec, params, x.reshape(R * T, -1))       # [R * T, V]

            def rows(a):             # a slot's value at each of its T rows
                return jnp.repeat(a, T)

            with jax.named_scope("sample"):
                toks = sample_rows(
                    logits, rows(temperatures), rows(top_ks), rows(active),
                    lambda: jax.vmap(_row_key)(rows(seeds),
                                               abs_pos.reshape(-1) + 1))
                return toks.reshape(R, T), new_caches

        return verify
