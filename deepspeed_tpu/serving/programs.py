"""Compiled serving programs: ONE jitted prefill and ONE jitted decode,
built through StepBuilder-style schedule composition.

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
  `[max_batch]`: per-slot block-table write + gather-based paged
  attention + per-slot sampling.  Every operation is row-wise
  (layernorm, per-row attention gather, per-row matmul dots, per-row
  RNG), which is the batching-invariance contract tier-1 pins: a
  request's tokens do not depend on WHICH other requests share the
  batch, so joining mid-flight is token-identical to decoding alone.
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

The attention math deliberately mirrors models/generation.py
`_block_with_cache` op for op (fp32 scores, the same einsum strings,
NEG_INF masking, probs cast to the cache dtype) so greedy serving output
is bit-identical to `generate()` when the cache lengths agree — pinned
in tests/test_serving.py.

Sampling determinism: the key for the token generated at absolute
position p is `fold_in(PRNGKey(request.seed), p)` — a pure function of
the request, never of the batch composition or the step count, so
sampled output is identical-under-seed across batch join/leave too.

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

from ..models.gpt import GPT, layer_norm
from ..utils.logging import logger
from .kv_cache import rows_for_tables

QUANT_MODES = ("none", "int8", "int4")

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

    def describe(self) -> str:
        cap = self.table_width * self.block_size
        q = "" if self.quantized == "none" else f", qwZ={self.quantized}"
        kv = "" if self.kv_dtype == "dense" else f", kv={self.kv_dtype}"
        spec = "" if not self.draft_len else \
            f", spec draft {self.draft_len}"
        return (f"serve schedule: decode[{self.max_batch} slots] + "
                f"prefill[chunk {self.prefill_chunk}], paged KV "
                f"{self.num_blocks} x {self.block_size} tok "
                f"(per-request cap {cap}){q}{kv}{spec}")

    def program_key(self):
        """The fields the COMPILED programs actually depend on.
        `num_blocks` is not one of them: the cache arrays are runtime
        inputs, a different pool size just retraces — so engines with
        different pool sizes can share one program pair."""
        return self._replace(num_blocks=0)


# -- sampling ---------------------------------------------------------------


def sample_token(logits, temperature, top_k, key):
    """One row: greedy at temperature 0, else temperature + optional
    top-k truncation, sampled with the caller's key.  `top_k`/
    `temperature` are per-request ARRAYS (not static), so one compiled
    program serves every request mix."""
    greedy = jnp.argmax(logits, axis=-1)
    v = logits.shape[-1]
    t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits.astype(jnp.float32) / t
    # dynamic top-k: value-threshold against the k-th largest logit
    # (ties at the threshold survive, the HF semantics generation.py
    # documents); top_k <= 0 disables the filter
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    kth = sorted_desc[jnp.clip(top_k, 1, v) - 1]
    filtered = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    sampled = jax.random.categorical(key, filtered, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _row_key(seed, position):
    """THE sampling-key rule: the token generated at absolute position
    p uses fold_in(PRNGKey(seed), p) — shared by prefill (first token)
    and decode so batch composition can never reach the RNG stream."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), position)


# -- paged attention block (mirrors generation._block_with_cache) -----------


def _gather_rows(table, block_size):
    """Block table [W] -> flat cache row indices [W * block_size]."""
    return (table[:, None] * block_size +
            jnp.arange(block_size)[None, :]).reshape(-1)


def _kv_write(c, idx, val, kv_mode):
    """Scatter `val` [N, H, Dh] into cache entry `c` at flat rows
    `idx`.  Dense: a plain row scatter at the cache's own dtype.
    Quantized: the rows are quantized through the PR-7 row kernels and
    BOTH the payload and the per-(row, head) scales scatter at the same
    indices — the write never touches another row's scale."""
    if kv_mode == "dense":
        return c.at[idx].set(val.astype(c.dtype))
    from ..runtime.comm.quant import quantize_rows

    payload, scales = c
    codes, s = quantize_rows(val.astype(jnp.float32), kv_mode)
    return (payload.at[idx].set(codes), scales.at[idx].set(s))


def _kv_read(c, rows, kv_mode):
    """Gather cache rows `rows` [B, L] -> [B, L, H, Dh].  Dense reads
    come back at the cache dtype (the downstream casts mirror
    generation._block_with_cache); quantized reads dequantize the
    gathered rows to fp32 in-program.  The single definition lives in
    kernels/paged.py — it doubles as the paged-attention oracle's
    gather, which is what keeps the registry's jnp path bit-identical
    to this program."""
    from ..kernels.paged import kv_read

    return kv_read(c, rows, kv_mode)


def _paged_block(p, cfg, x, ck, cv, write_idx, rows, q_pos,
                 kv_mode="dense", block_size=0):
    """One decoder block over x [B, T, D] with paged KV.

    `write_idx` [B*T] flat cache rows this chunk's K/V land in, `rows`
    [B, L] flat cache rows the attention reads (the gathered block
    table), `q_pos` [B, T] absolute positions of x's tokens.  Op-for-op
    the math of generation._block_with_cache; only the cache addressing
    differs (scatter/gather through the table instead of
    dynamic_update_slice on a contiguous cache).  `kv_mode` picks the
    storage codec: "dense" stores rows at the cache arrays' dtype,
    "int8"/"int4" stores (payload, scales) pairs dequantized at the
    gather — the surrounding math is identical either way, so parity
    pins hold AT MATCHED kv_mode.
    """
    B, T, D = x.shape
    H, Dh = cfg.num_heads, cfg.head_dim
    h = layer_norm(x, p["ln1"], cfg.layer_norm_eps)
    qkv = h @ p["attn"]["qkv"]["w"].astype(h.dtype) + \
        p["attn"]["qkv"]["b"].astype(h.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = lambda t: t.reshape(B, T, H, Dh)
    q, k, v = shape(q), shape(k), shape(v)
    ck = _kv_write(ck, write_idx, k.reshape(B * T, H, Dh), kv_mode)
    cv = _kv_write(cv, write_idx, v.reshape(B * T, H, Dh), kv_mode)
    # attention core through the kernel registry: the jnp oracle
    # (kernels/paged.py paged_attention_reference) is this block's
    # pre-registry gather/einsum/softmax chain op-for-op — wherever the
    # oracle is chosen, serving output is bit-identical; the Pallas
    # kernel fuses the table gather (+ quantized-KV dequant) into an
    # online-softmax sweep over cache blocks
    from ..kernels import registry

    attn = registry.dispatch(
        "paged_attention", q, ck, cv, rows, q_pos,
        info={"block_size": block_size, "kv_len": rows.shape[1],
              "q_len": T, "head_dim": Dh, "kv_mode": kv_mode},
        kv_mode=kv_mode, block_size=block_size)
    attn = attn.reshape(B, T, D)
    attn = attn @ p["attn"]["proj"]["w"].astype(h.dtype) + \
        p["attn"]["proj"]["b"].astype(h.dtype)
    x = x + attn
    h = layer_norm(x, p["ln2"], cfg.layer_norm_eps)
    h = h @ p["mlp"]["fc1"]["w"].astype(h.dtype) + \
        p["mlp"]["fc1"]["b"].astype(h.dtype)
    h = jax.nn.gelu(h, approximate=True)
    h = h @ p["mlp"]["fc2"]["w"].astype(h.dtype) + \
        p["mlp"]["fc2"]["b"].astype(h.dtype)
    return x + h, ck, cv


def _proj_logits(cfg, params, x_rows):
    """[B, D] hidden rows -> fp32 logits [B, V] (generation.py's head)."""
    w = (params["wte"].T if cfg.tie_embeddings else params["lm_head"])
    return (x_rows @ w.astype(x_rows.dtype)).astype(jnp.float32)


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
    returned arrays back through PagedKVCache.caches."""

    def __init__(self, model: GPT, schedule: ServeSchedule):
        cfg = model.config
        if cfg.num_experts > 1 or cfg.pipeline_stages > 1:
            raise NotImplementedError(
                "the serving engine supports plain dense GPT configs "
                "(no MoE layers, no pipeline-stacked blocks) — the "
                "generate() contract")
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
        self.model = model
        self.schedule = schedule

    def build(self) -> dict:
        logger.info(self.schedule.describe())
        return {"schedule": self.schedule,
                "prefill": self._build_prefill(),
                "decode": self._build_decode(),
                "verify": self._build_verify(),
                "prepare_params": self._prepare_params}

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
        cfg = self.model.config
        s = self.schedule
        C, bs, W = s.prefill_chunk, s.block_size, s.table_width

        @partial(jax.jit, donate_argnums=(1,))
        def prefill(params, caches, tokens, pos, n_valid, table,
                    temperature, top_k, seed):
            """tokens [1, C] (zero-padded past n_valid) at absolute
            position `pos`; writes the chunk's K/V through `table`
            [W] and returns (first-token sample, last-valid-row
            logits, caches).  The sample is only meaningful on the
            FINAL chunk (the engine ignores it otherwise)."""
            params = self._maybe_dequant(params)
            abs_pos = pos + jnp.arange(C)
            # per-row gather, NOT dynamic_slice_in_dim(wpe, pos, C):
            # when the final chunk's pad rows run past the wpe table,
            # a dynamic slice CLAMPS its start backwards and shifts the
            # VALID rows onto wrong positional embeddings (silently
            # breaking the ==generate() contract); the gather keeps
            # every valid row exact and only pad rows (overwritten
            # before read / masked) see the clamped last entry
            wpe_rows = params["wpe"][
                jnp.clip(abs_pos, 0, params["wpe"].shape[0] - 1)]
            x = params["wte"][tokens] + wpe_rows[None]
            blk_i = abs_pos // bs
            # positions past the table (pad rows of the final chunk)
            # write to the trash block, never a neighbour's memory
            blk = jnp.where(blk_i < W, table[jnp.clip(blk_i, 0, W - 1)], 0)
            write_idx = blk * bs + abs_pos % bs
            rows = _gather_rows(table, bs)[None, :]
            q_pos = abs_pos[None, :]
            new_caches = []
            for bp, (ck, cv) in zip(params["blocks"], caches):
                x, ck, cv = _paged_block(bp, cfg, x, ck, cv, write_idx,
                                         rows, q_pos,
                                         kv_mode=s.kv_dtype,
                                         block_size=bs)
                new_caches.append((ck, cv))
            x = layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
            last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
            logits = _proj_logits(cfg, params, last[:, 0, :])  # [1, V]
            key = _row_key(seed, pos + n_valid)
            tok = sample_token(logits[0], temperature, top_k, key)
            return tok, logits[0], new_caches

        return prefill

    def _build_decode(self):
        cfg = self.model.config
        s = self.schedule
        bs = s.block_size

        @partial(jax.jit, donate_argnums=(1,))
        def decode(params, caches, tokens, positions, active, tables,
                   temperatures, top_ks, seeds):
            """One token for every slot: tokens [R] (each slot's last
            token), positions [R] (its write position = current cached
            length), active [R] bool, tables [R, W], sampling params
            [R].  Inactive slots write to the trash block and their
            outputs are discarded by the engine — all slot math is
            row-wise, THE batching-invariance contract."""
            params = self._maybe_dequant(params)
            R = tokens.shape[0]
            x = (params["wte"][tokens] +
                 params["wpe"][positions])[:, None, :]       # [R, 1, D]
            blk_i = positions // bs
            blk = jnp.take_along_axis(
                tables, jnp.clip(blk_i, 0, s.table_width - 1)[:, None],
                axis=1)[:, 0]
            write_idx = jnp.where(active, blk * bs + positions % bs, 0)
            rows = rows_for_tables(tables, bs)
            q_pos = positions[:, None]
            new_caches = []
            for bp, (ck, cv) in zip(params["blocks"], caches):
                x, ck, cv = _paged_block(bp, cfg, x, ck, cv, write_idx,
                                         rows, q_pos,
                                         kv_mode=s.kv_dtype,
                                         block_size=bs)
                new_caches.append((ck, cv))
            x = layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
            logits = _proj_logits(cfg, params, x[:, -1, :])  # [R, V]
            keys = jax.vmap(_row_key)(seeds, positions + 1)
            toks = jax.vmap(sample_token)(logits, temperatures, top_ks,
                                          keys)
            return toks, new_caches

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
        cfg = self.model.config
        s = self.schedule
        bs, W = s.block_size, s.table_width
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
            params = self._maybe_dequant(params)
            R = tokens.shape[0]
            abs_pos = positions[:, None] + jnp.arange(T)[None, :]
            # per-row gather with a clip, the prefill rule: pad rows
            # past the wpe table clamp (their writes land in trash and
            # their samples are discarded by the engine)
            wpe_rows = params["wpe"][
                jnp.clip(abs_pos, 0, params["wpe"].shape[0] - 1)]
            x = params["wte"][tokens] + wpe_rows          # [R, T, D]
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
            rows = rows_for_tables(tables, bs)
            q_pos = abs_pos
            new_caches = []
            for bp, (ck, cv) in zip(params["blocks"], caches):
                x, ck, cv = _paged_block(bp, cfg, x, ck, cv, write_idx,
                                         rows, q_pos,
                                         kv_mode=s.kv_dtype,
                                         block_size=bs)
                new_caches.append((ck, cv))
            x = layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
            logits = _proj_logits(
                cfg, params,
                x.reshape(R * T, -1)).reshape(R, T, -1)   # [R, T, V]
            keys = jax.vmap(jax.vmap(_row_key, in_axes=(None, 0)))(
                seeds, abs_pos + 1)
            toks = jax.vmap(jax.vmap(
                sample_token, in_axes=(0, None, None, 0)))(
                logits, temperatures, top_ks, keys)
            return toks, new_caches

        return verify
