"""Which kernel a call runs: the one place that decides, from what the
call can see.

Every hot inner loop that has a Pallas implementation registers a
`KernelOp` (the reference's op_builder pattern) with:

* `pallas(...)`   — the Pallas TPU kernel (runs under the Pallas
  interpreter off-TPU, which is how tier-1 pins parity on CPU);
* `oracle(...)`   — the plain jnp expression of the same mathematics
  (it IS the correctness contract: exact for the integer codecs and MoE
  permutations, tolerance-bounded for attention);
* `is_compatible()` / `compatibility_message()` — the probe: a TPU
  backend, and the op's `DS_KERNEL_{NAME}` environment switch not "0"
  (the `DS_BUILD_*` convention from ops/op_builder/builder.py, and the
  one override an operator has);
* `auto_supports(variant, info)` — the per-call shape rule over the
  `info` dict the call site builds from its operands: what the chip's
  compiler takes (tests/test_tpu_compile.py compiles every case for a
  described v5e) and where the kernel beats the oracle on the chip.

    call site (ops/transformer/attention.py, serving/layers.py,
               serving/sparse.py, runtime/comm/quant.py,
               moe/dispatch.py, moe/dropless.py,
               models/granite_hybrid.py, models/qwen3_next.py,
               ops/sparse_attention/)
       └─> dispatch(op, *args, info=<shape facts of this call>)
              └─> pallas  iff  TPU backend  and  op.auto_supports(info)
                               and  partitionable here
                               and  DS_KERNEL_<OP> != 0
                  oracle  otherwise

A forced implementation exists in two forms only: the call-site `impl=`
(what a model config's `attn_impl`, `SparseSelfAttention(impl=)` carry)
and the scoped `with kernel_config(...)` that tests open.
Nothing is installed for the process by an engine, a config file or a
tuner.

* `"auto"`  — as drawn above.  Nothing `auto` selects may be refused by
  the compiler.
* `"pallas"` — the kernel, NO silent fallback: on a TPU a call the
  probe or the shape rule refuses raises with that reason, and the
  compiler is never asked; off-TPU this raises loudly unless the
  interpret escape is set (`kernel_config(interpret=True)`, or the
  call-site `interpret_ok=True` with which training attention and
  `SparseSelfAttention` keep running a forced kernel under the
  interpreter).
* `"jnp"` (alias `"xla"`) — the oracle, unconditionally.

Every `dispatch()` bumps `kernel.dispatches` (pallas chosen) or
`kernel.fallbacks` (oracle chosen).  Like the `dist.*` family these are
TRACE-time counts — once per compiled program per call site, not per
execution — so a decode program that retraces shows exactly its
per-layer dispatch count (docs/tutorials/kernels.md).

Implementation modules are imported lazily from the op methods so the
registry itself stays import-cycle-free.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Dict, Mapping, Optional, Tuple

import jax

from ..monitor.counters import COUNTERS
from ..ops import pallas_backend

KERNEL_IMPLS = ("auto", "pallas", "jnp")
# legacy spelling accepted at call sites (SparseSelfAttention's
# impl="xla") — normalized to "jnp" before resolution
_IMPL_ALIASES = {"xla": "jnp"}


# what jax 0.9's Pallas TPU lowering raises for a block it cannot tile
_BLOCK_RULE = ("\"The Pallas TPU lowering currently requires that the "
               "last two dimensions of your block shape are divisible by "
               "8 and 128 respectively, or be equal to the respective "
               "dimensions of the overall array\"")


def _on_tpu() -> bool:
    return not pallas_backend.interpret()


# ---------------------------------------------------------------------------
# op classes (op_builder pattern: NAME + compatibility probe per op)
# ---------------------------------------------------------------------------


class KernelOp:
    """One registered hot-loop op.  Subclasses lazily import their
    implementation module inside `pallas()`/`oracle()` — registration
    stays cheap and cycle-free."""

    NAME = "base"
    VARIANTS: Tuple[str, ...] = ("default",)
    # False when pallas<->oracle parity is tolerance-bounded (attention
    # reduction order); True when bit-exact (integer codecs, gathers)
    EXACT = False

    def env_enabled(self) -> bool:
        return os.environ.get(f"DS_KERNEL_{self.NAME.upper()}",
                              "1") != "0"

    def is_compatible(self) -> bool:
        """Pallas-on-TPU probe: native selection needs a TPU backend
        and the op's env switch left on."""
        return self.env_enabled() and _on_tpu()

    def compatibility_message(self) -> str:
        if not self.env_enabled():
            return (f"disabled via DS_KERNEL_{self.NAME.upper()}=0")
        if not _on_tpu():
            return (f"backend is {jax.default_backend()!r}, not 'tpu' "
                    f"(the Pallas kernel only runs natively on TPU; "
                    f"off-TPU it needs the interpret escape)")
        return "compatible"

    def auto_supports(self, variant: str, info: Optional[Mapping]
                      ) -> Tuple[bool, str]:
        """Per-call shape rule (info is the call site's shape dict;
        None = no constraint data, assume yes).  False means the chip's
        compiler, or the kernel itself, refuses the shape: `auto` takes
        the oracle and a forced `pallas` raises the reason."""
        return True, ""

    def unpartitionable(self) -> str:
        """Why a native kernel cannot be traced HERE, or "".  XLA does
        not partition a Mosaic kernel: under `jit` over a mesh of several
        devices the call must sit inside a `shard_map` that is manual
        over every axis of size > 1 (the wire codecs' calls do)."""
        from ..comm.mesh import peek_mesh

        info = peek_mesh()
        auto = info.auto_axes() if info is not None else []
        if not auto:
            return ""
        return (f"the call is traced outside a shard_map over mesh axes "
                f"{auto}, and \"Mosaic kernels cannot be automatically "
                f"partitioned\"")

    def check_variant(self, variant: str) -> None:
        if variant not in self.VARIANTS:
            raise ValueError(
                f"kernels.{self.NAME}: unknown variant {variant!r}; "
                f"valid: {self.VARIANTS}")

    def pallas(self, variant: str, *args, **kwargs):
        raise NotImplementedError

    def oracle(self, variant: str, *args, **kwargs):
        raise NotImplementedError


# Below this XLA's fused attention wins.  Measured on a v5e, forward +
# backward of 16k tokens at 25 heads of 64, bf16 (PERF.md §6, PR 27):
# S 256: XLA 1.02 ms, flash 1.51; S 512: XLA 2.43, flash 1.55; S 1024:
# XLA 4.69, flash 1.99.  (384 is not measured and stays with XLA.)
_FLASH_MIN_SEQ = 512


class FlashAttentionOp(KernelOp):
    """Dense attention of a training step, causal or full (op 4):
    ops/transformer/flash_attention.py's kernels against
    `xla_attention`, the fp32-softmax einsum chain.  Both take BSHD
    `[batch, seq, heads, head_dim]`; parity is tolerance-bounded (online
    softmax over tiles against one fused softmax).  The shape rule reads
    `ops/transformer/attention.py::flash_info`: the two lengths, the
    head size and the kind of bias."""

    NAME = "flash_attention"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        if info.get("bias", "none") == "full":
            return False, ("the kernel adds a per-key bias [B, 1, 1, Sk] "
                           "only, and returns no cotangent for it: a full "
                           "[.., S, Sk] bias, or one being differentiated, "
                           "goes through XLA")
        s, sk = int(info["seq_len"]), int(info["kv_len"])
        if s < _FLASH_MIN_SEQ:
            return False, (f"seq_len {s} < {_FLASH_MIN_SEQ}: XLA's fused "
                           f"attention is faster on the chip")
        d = int(info["head_dim"])
        if d not in (64, 128, 256):
            return False, f"head_dim {d} not in (64, 128, 256)"
        from ..ops.transformer.flash_attention import flash_blocks

        bq, bk = flash_blocks(s, sk)
        if s % bq or sk % bk:
            return False, (f"seq lens ({s},{sk}) are not whole tiles of "
                           f"({bq},{bk}), the multiples of 128 that "
                           f"`flash_blocks` cuts them into")
        return True, ""

    def unpartitionable(self) -> str:
        # `pallas()` calls the kernel once per shard, under a shard_map
        # of its own over whatever mesh axes are not manual already
        return ""

    def pallas(self, variant, *args, **kwargs):
        from ..ops.transformer import attention
        return attention.flash_per_shard(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from ..ops.transformer import attention
        return attention.xla_oracle(*args, **kwargs)


class SparseAttentionOp(KernelOp):
    """Block-sparse attention under a SparsityConfig layout (satellite:
    the ad-hoc impl=auto|pallas|xla selection from
    ops/sparse_attention/sparse_attention.py folded into the registry).
    Pallas = flash_sparse_attention, oracle = block_sparse_attention."""

    NAME = "sparse_attention"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        # the historical auto heuristic, verbatim: kernel only for
        # plain (bias-free) calls with MXU-shaped blocks and head dims
        if not info.get("plain", True):
            return False, "biases route to the XLA gather path"
        block = int(info.get("block", 0))
        if block % 128 != 0:
            return False, f"layout block {block} not a multiple of 128"
        d = int(info.get("head_dim", 0))
        if d not in (64, 128, 256):
            return False, f"head_dim {d} not in (64, 128, 256)"
        return True, ""

    def pallas(self, variant, q, k, v, layout, block, *, causal=False,
               key_padding_bias=None, attn_bias=None, dropout_rate=0.0,
               dropout_rng=None):
        from ..ops.sparse_attention.flash_sparse import \
            flash_sparse_attention
        # the kernel has no bias path; auto never selects it with
        # biases and the module wrapper routes biased calls to the
        # oracle (the historical silent-XLA behaviour, now explicit)
        return flash_sparse_attention(
            q, k, v, layout, block, causal=causal,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng)

    def oracle(self, variant, q, k, v, layout, block, *, causal=False,
               key_padding_bias=None, attn_bias=None, dropout_rate=0.0,
               dropout_rng=None):
        from ..ops.sparse_attention.sparse_attention import \
            block_sparse_attention
        return block_sparse_attention(
            q, k, v, layout, block, causal_token_mask=causal,
            key_padding_bias=key_padding_bias, attn_bias=attn_bias,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng)


def _copies_blocks(info: Mapping) -> Tuple[bool, str]:
    """Whether a block of the call's rows is a slab kernels/paged.py's
    walks can copy where it lies: dense rows, whole sublane tiles."""
    mode = info.get("kv_mode", "dense")
    bs = int(info.get("block_size", 0))
    if mode != "dense":
        return False, (f"{mode} rows: the kernel would copy a "
                       f"block's ({bs}, {int(info.get('num_heads', 1))}) "
                       f"tile of scales, and "
                       f"\"Slice shape along dimension 2 must be "
                       f"aligned to tiling (128)\"; the oracle "
                       f"dequantises the gathered rows")
    item = int(info.get("kv_itemsize", 2))
    sublanes = 32 // item
    if bs <= 0 or bs % sublanes:
        return False, (f"a block of {bs} rows is not whole tiles of "
                       f"{sublanes} rows at {item} bytes a value, so "
                       f"it is no slab the kernel can copy")
    return True, ""


def _walk_supports(info: Mapping) -> Tuple[bool, str]:
    """What kernels/paged.py's walk of live blocks takes, from what a
    serving call site observes (serving/layers.py::paged_info): the
    tile is `q_len` x `num_heads` score rows of a cache row's lanes —
    the row's `kv_heads` heads where it has fewer than the queries."""
    from .paged import STEP_QUERIES, tile_blocks

    t = int(info.get("q_len", 1))
    if t > STEP_QUERIES:
        return False, (f"q_len {t} is a prefill chunk: the kernel "
                       f"unrolls over a decode or verify step's "
                       f"queries (<= {STEP_QUERIES}); prefill reads one "
                       f"request's rows through the jnp oracle")
    ok, why = _copies_blocks(info)
    if not ok:
        return ok, why
    from ..serving.kv_cache import pool_width

    bs, item = int(info["block_size"]), int(info.get("kv_itemsize", 2))
    H = int(info.get("num_heads", 1))
    width = pool_width(int(info.get("kv_heads", H)),
                       int(info.get("head_dim", 128)))
    if not tile_blocks(bs, int(info.get("table_width", 1)),
                       width * item, t, H, width):
        return False, (f"{t} x {H} score rows of {width} lanes, or "
                       f"one block of {bs} such rows, do not fit the "
                       f"kernel's VMEM tiles")
    return True, ""


class PagedAttentionOp(KernelOp):
    """Decode-path paged attention (op 1): a walk of each slot's live
    blocks in the PagedKVCache's pool, all heads a tile, online softmax
    (kernels/paged.py).  Oracle = the gather/einsum/softmax expression
    serving/layers.py's `_paged_attend` ran before there was a kernel
    (serving stays bit-identical to `generate()` wherever the oracle is
    chosen).  The shape rule looks at what the call site can observe —
    `q_len`, `kv_mode`, `block_size`, the row's width and dtype, the
    table's width — never at a head size or a model.  A row whose
    `kv_heads` heads each serve several query heads takes the same walk
    at a GROUPED tile — the row's heads as they lie, every (query, query
    head) pair a score row in the lanes of the K/V head it reads — as
    `grouped_attention` below, whose oracle is another expression; a
    latent row, key and value at once, is that tile at one K/V head
    (`latent_attention`)."""

    NAME = "paged_attention"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        return _walk_supports(info)

    def pallas(self, variant, *args, **kwargs):
        from . import paged
        return paged.paged_attention_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from . import paged
        return paged.paged_attention_reference(*args, **kwargs)


def _prefill_walk_supports(info: Mapping) -> Tuple[bool, str]:
    """What kernels/paged.py's walk for a prefill chunk takes
    (`_prefill_walk`): ONE request's queries, a K/V head's query rows
    against that head's 128-lane slices of a tile of the row."""
    from .paged import prefill_tiles

    B, t = int(info.get("batch", 1)), int(info["q_len"])
    if B != 1:
        return False, (f"{B} sequences of {t} queries: the prefill walk "
                       f"runs one request's table, its grid the tiles of "
                       f"that request's query positions")
    ok, why = _copies_blocks(info)
    if not ok:
        return ok, why
    H = int(info.get("num_heads", 1))
    KV, Dh = int(info.get("kv_heads", H)), int(info.get("head_dim", 128))
    if Dh % 128 or H % KV:
        return False, (f"{H} query heads on {KV} K/V heads of {Dh} values: "
                       f"the prefill walk multiplies a K/V head's queries "
                       f"with that head's lanes of a tile, which must be "
                       f"whole 128-lane tiles of whole groups")
    if not prefill_tiles(t, H, KV, Dh, int(info["block_size"]),
                         int(info.get("table_width", 1)),
                         int(info.get("kv_itemsize", 2))):
        return False, (f"no tile of whole sublanes of query positions "
                       f"divides the {t} of the chunk and fits the "
                       f"kernel's VMEM at {H} heads of {Dh}")
    return True, ""


class GroupedAttentionOp(KernelOp):
    """Attention over paged rows of `kv_heads` heads that each serve
    `num_heads / kv_heads` query heads (serving/layers.py
    `_grouped_attend`).  Pallas, a decode or verify step: the paged walk
    at a grouped tile, the row's heads as they lie, `q_len` x
    `num_heads` score rows whose queries sit in the lanes of the K/V
    head they read.  Pallas, a prefill chunk (`q_len` over
    `paged.STEP_QUERIES`): the one request's live blocks from the
    table's first entry to the one each tile of query positions needs,
    a K/V head's queries against that head's lanes (kernels/paged.py
    `grouped_attention_pallas` for both).  Oracle = the gather of every
    table entry and `attend_grouped` under the layer's visibility mask,
    the expression the layer ran before there was a kernel
    (`grouped_attention_reference`).  A sliding layer's call
    (`grouped_info`'s `window`, and `ring` where its rows lie in a ring)
    takes the same walks over the window's live blocks, modulo the run:
    consecutive blocks from the one that holds the oldest query's lower
    bound, walked row k at position `base + k`, two bounds a query.  On
    the table itself no position laps another and nothing more is asked.
    Over a ring the mask by position holds where no row a query sees was
    overwritten by the call's newest position: a run of `window + q_len
    - 1` rows — the engine's `window + prefill_chunk` passes for its
    chunk.  That is all a prefill chunk needs (its walk may copy the
    block that is the run's oldest and newest at once at both of its
    ends); a decode or verify step's walk is held to the run's own
    count of blocks and starts at a block's first row, so it needs
    `block_size` rows more.  A shorter ring keeps the gather.  The
    shape rule is then the walk's at its tile; a prefill chunk's
    besides: one request, heads of whole 128-lane tiles, tiles that fit
    VMEM.  The paged, latent and EVA ops keep `q_len` <=
    `STEP_QUERIES`: their prefill is ROADMAP S11's later cases."""

    NAME = "grouped_attention"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        from .paged import STEP_QUERIES

        t, window = int(info.get("q_len", 1)), int(info.get("window", 0))
        chunk = t > STEP_QUERIES
        ok, why = _prefill_walk_supports(info) if chunk \
            else _walk_supports(info)
        if ok and info.get("ring"):
            bs = int(info["block_size"])
            run = int(info.get("table_width", 1)) * bs
            # (a step's walk, held to the run's own count of blocks from
            # a block's first row, needs a block more)
            need = window + t - 1 + (0 if chunk else bs)
            if run < need:
                return False, (
                    f"a ring of {run} rows under a window of {window}: the "
                    f"walk masks a block's rows by position, which holds "
                    f"where no row a query of the call's {t} sees was "
                    f"overwritten by a newer lap — a run of {need} rows or "
                    f"more; a shorter one keeps the gather")
        return ok, why

    def pallas(self, variant, *args, **kwargs):
        from . import paged
        return paged.grouped_attention_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from ..serving import layers
        return layers.grouped_attention_reference(*args, **kwargs)


class LatentAttentionOp(KernelOp):
    """Absorbed attention over paged latent rows — one array a layer that
    is key and value at once, one "K/V head" a row for every query head
    (serving/layers.py `_latent_attend`, a decode step).  Pallas = the
    paged walk at `kv_heads` = 1 with one operand: a block is copied
    once, scored and summed over as the one tile (kernels/paged.py
    `latent_attention_pallas`).  Oracle = the gather of every table entry
    and `attend_rows` under the causal mask, the expression the layer ran
    before there was a kernel (`latent_attention_reference`).  The shape
    rule is the walk's at that tile (`latent_info`)."""

    NAME = "latent_attention"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        return _walk_supports(info)

    def pallas(self, variant, *args, **kwargs):
        from . import paged
        return paged.latent_attention_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from ..serving import layers
        return layers.latent_attention_reference(*args, **kwargs)


class MaskedLatentAttentionOp(KernelOp):
    """A prefill chunk's attention over a learned selection of latent
    rows (serving/sparse.py `sparse_latent_attend`: a mask [queries,
    table positions] over tiles of the one request's rows, each expanded
    through W_kv_b).  Pallas = one program a head that walks the tiles
    with that head's scores, probabilities and running softmax in VMEM
    (kernels/masked_latent.py); oracle = `attend_tiles`, the same walk
    in `jax.numpy`, every tile's `[heads, queries, tile]` scores through
    HBM.  The shape rule (`serving/sparse.py::masked_info`): one
    request, rows and weights of two bytes a value, queries in whole
    sublane tiles of the int8 mask, every width the kernel slices a
    whole number of lane tiles, tiles that fit its VMEM."""

    NAME = "masked_latent_attention"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        from .masked_latent import _VMEM, masked_vmem

        B, t, tile = (int(info[k]) for k in ("batch", "q_len", "tile"))
        if B != 1:
            return False, (f"{B} sequences of {t} queries: the kernel walks "
                           f"one request's table")
        item = int(info["kv_itemsize"])
        if item != 2 or int(info["w_itemsize"]) != 2:
            return False, (f"rows of {item} and weights of "
                           f"{int(info['w_itemsize'])} bytes a value: the "
                           f"kernel's products take both at two")
        rank, qk, v = int(info["rank"]), \
            int(info["nope"]) + int(info["rope"]), int(info["v"])
        if t % 32 or tile % 128 or rank % 128 or qk % 128 or v % 128:
            return False, (f"{t} queries over tiles of {tile} positions, "
                           f"latent rows of {rank}, keys of {qk} and values "
                           f"of {v} a head: the mask's tiles are (32, 128) "
                           f"and every slice of the kernel whole 128-lane "
                           f"tiles")
        need = masked_vmem(t, tile, rank, qk, v, item)
        if need > _VMEM:
            return False, (f"a tile of {tile} rows expanded for one head and "
                           f"{t} x {tile} scores need {need >> 20} MiB of "
                           f"VMEM, over the kernel's {_VMEM >> 20}")
        return True, ""

    def pallas(self, variant, *args, **kwargs):
        from . import masked_latent
        return masked_latent.masked_latent_attention_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from ..serving import sparse
        return sparse.attend_tiles(*args, **kwargs)


class EvaAttentionOp(KernelOp):
    """Chunk-summarised attention over the paged cache (kernels/eva.py):
    a window of exact rows and the summary rows of closed windows in one
    softmax.  Pallas = the paged walk over the two runs of table entries
    a slot's position makes live; oracle = the gather of the whole table
    under the visibility mask.  The shape rule is the walk's, and that
    both runs are whole blocks (serving/layers.py::eva_info)."""

    NAME = "eva_attention"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        ok, why = _walk_supports(info)
        if not ok:
            return ok, why
        bs, window = int(info["block_size"]), int(info["window"])
        rows = window // int(info["chunk"])
        if window % bs or rows % bs:
            return False, (f"a window of {window} rows and its {rows} "
                           f"summary rows are not whole blocks of {bs}: "
                           f"the walk takes a run's blocks whole")
        return True, ""

    def pallas(self, variant, *args, **kwargs):
        from . import eva
        return eva.eva_attention_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from . import eva
        return eva.eva_attention_reference(*args, **kwargs)


class QuantCodecOp(KernelOp):
    """Blockwise int8/int4 quantize/dequantize (op 2, the ZeRO++ wire
    codec from runtime/comm/quant.py).  Variants: "quantize" /
    "dequantize".  Parity is BIT-exact: the kernel runs the oracle's
    op sequence (subnormal flush -> finite amax -> fp16 scale ->
    round/clip -> non-finite marker) tile-by-tile; `pack_wire`/
    `unpack_wire` bitcast glue rides in the wrappers unchanged."""

    NAME = "quant_codec"
    VARIANTS = ("quantize", "dequantize")
    EXACT = True

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        block = int(info.get("block", 0))
        if block % 128:
            return False, (f"quant block {block} not lane-aligned "
                           f"(128)")
        return True, ""

    def pallas(self, variant, *args, **kwargs):
        from . import quant_codec
        if variant == "quantize":
            return quant_codec.quantize_blockwise_pallas(*args, **kwargs)
        return quant_codec.dequantize_blockwise_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from ..runtime.comm import quant
        if variant == "quantize":
            return quant.quantize_blockwise_ref(*args, **kwargs)
        return quant.dequantize_blockwise_ref(*args, **kwargs)


class MoEDispatchOp(KernelOp):
    """Sort-based MoE token movement (op 3, moe/dispatch.py).  Variants:
    "dispatch" (tokens -> [E, C, D] buckets; the kernel reformulates
    the oracle's scatter-add — whose kept destinations are unique — as
    a per-slot gather through a precomputed inverse permutation, so
    parity is BIT-exact) and "combine" (gated gather-back in the same
    term order; ~1-ulp tolerance, the accumulator may fuse an FMA).
    """

    NAME = "moe_dispatch"
    VARIANTS = ("dispatch", "combine")
    EXACT = True

    # Both kernels move ONE token row per grid step, and a gather has
    # no 8-row tile to move instead (kernels/moe_kernels.py): until
    # they are rewritten they run under the interpreter only, and the
    # chip gets the jnp scatter/gather.

    def is_compatible(self) -> bool:
        return False

    def compatibility_message(self) -> str:
        if self.env_enabled() and _on_tpu():
            return (f"the kernel moves tokens in one-row blocks "
                    f"(1, model_dim), which the chip's compiler refuses: "
                    f"{_BLOCK_RULE}")
        return super().compatibility_message()

    def pallas(self, variant, *args, **kwargs):
        from . import moe_kernels
        if variant == "dispatch":
            return moe_kernels.sorted_dispatch_pallas(*args, **kwargs)
        return moe_kernels.sorted_combine_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from ..moe import dispatch as moe_dispatch
        if variant == "dispatch":
            return moe_dispatch.sorted_dispatch_ref(*args, **kwargs)
        return moe_dispatch.sorted_combine_ref(*args, **kwargs)


class TouchedExpertsOp(KernelOp):
    """The routed FFN of a call of few rows (moe/dropless.py): every
    expert that a live row's assignment chose, on every row, under the
    call's combine weights.  Pallas = a walk of the touched list that
    reads only those experts' matrices (kernels/moe_kernels.py); oracle
    = `experts_weighted`, every expert held under the same weights.  The
    shape rule looks at the call's rows, the experts' three sizes and
    how many matrices an expert has (`moe/dropless.py::touched_info`)."""

    NAME = "touched_experts"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        from ..moe.dropless import RIDGE_TOKENS
        from .moe_kernels import touched_tile

        t = int(info["tokens"])
        if t > RIDGE_TOKENS:
            return False, (f"{t} rows are over the ridge "
                           f"({RIDGE_TOKENS}): every expert's products "
                           f"on every row cost more than the bytes they "
                           f"save, and the grouped products do top_k a "
                           f"row")
        D, F = int(info["model_dim"]), int(info["expert_dim"])
        if D % 128:
            return False, (f"rows of {D} values are not whole 128-lane "
                           f"tiles")
        m = int(info.get("matrices", 3))
        if not touched_tile(D, F, int(info["itemsize"]), m):
            return False, (f"no whole-tile share of an expert's {F} "
                           f"columns divides them and fits the kernel's "
                           f"VMEM at {D} rows and {m} matrices an expert")
        return True, ""

    def pallas(self, variant, *args, **kwargs):
        from . import moe_kernels
        return moe_kernels.touched_experts_pallas(*args, **kwargs)

    def oracle(self, variant, x, experts, w, ids, n):
        from ..moe import dropless
        return dropless.experts_weighted(x, experts, w)


class GroupedExpertsOp(KernelOp):
    """The routed FFN of a slab of rows sorted by expert (moe/dropless.py
    `experts_slabs`, a prefill chunk): each row through the FFN of the
    expert whose range of the slab holds it.  Pallas = a walk of the
    held experts over their ranges that reads every expert's matrices
    once, slab and result in VMEM (kernels/moe_kernels.py); oracle =
    `grouped_ffn`, XLA's grouped products (`lax.ragged_dot`) over the
    same rows.  The shape rule looks at the call's rows, the slab's and
    the experts' three sizes (`moe/dropless.py::grouped_info`)."""

    NAME = "grouped_experts"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        from ..moe.dropless import RIDGE_TOKENS
        from .moe_kernels import grouped_tile

        t = int(info["tokens"])
        if t <= RIDGE_TOKENS:
            return False, (f"{t} rows are under the ridge "
                           f"({RIDGE_TOKENS}): a call bound by the "
                           f"experts' bytes reads the touched ones under "
                           f"a mask, and sorts nothing")
        D, F = int(info["model_dim"]), int(info["expert_dim"])
        if D % 128:
            return False, (f"rows of {D} values are not whole 128-lane "
                           f"tiles")
        rows = int(info["rows"])
        m = int(info.get("matrices", 3))
        if not grouped_tile(rows, D, F, int(info["itemsize"]), m):
            return False, (f"a slab of {rows} rows of {D} values, its "
                           f"float32 result and a whole-tile share of an "
                           f"expert's {F} columns, {m} matrices an expert, "
                           f"do not fit the kernel's VMEM together")
        return True, ""

    def pallas(self, variant, *args, **kwargs):
        from . import moe_kernels
        return moe_kernels.grouped_experts_pallas(*args, **kwargs)

    def oracle(self, variant, *args, **kwargs):
        from ..moe import dropless
        return dropless.grouped_ffn(*args, **kwargs)


class SsmStepOp(KernelOp):
    """The Mamba-2 recurrence of a decode step (models/granite_hybrid.py
    `ssm_mix`, one token a slot).  Pallas = a walk of the step's live
    slots that reads and writes only their state, in place
    (kernels/ssm.py); oracle = `ssm_step`, every slot's state through
    the same expression, a slot that is not live under dt = 0.  The
    shape rule looks at the state's four sizes (`kernels/ssm.py::
    ssm_step_info`)."""

    NAME = "ssm_step"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        from .ssm import groups_fit, head_tile

        P, N, item = (int(info[k]) for k in ("head_dim", "state",
                                             "itemsize"))
        if item != 4 or P % 8 or N % 128:
            return False, (f"a head's state of {P} x {N} values of {item} "
                           f"bytes is not whole (8, 128) tiles of float32")
        th = head_tile(int(info["heads"]), P, N)
        if not th:
            return False, (f"one head's state of {P} x {N} float32, read "
                           f"and written and double-buffered, does not fit "
                           f"the kernel's VMEM")
        if th * P % 128:
            return False, (f"the {th} heads of {P} rows a block that fit "
                           f"the kernel's VMEM are not whole tiles of 128 "
                           f"rows")
        G = int(info.get("groups", 1))
        if not groups_fit(int(info["heads"]), P, G, th):
            return False, (f"{G} groups of {int(info['heads']) // G} heads "
                           f"of {P} rows do not fall on blocks of 128 rows "
                           f"and tiles of {th} heads")
        return True, ""

    def pallas(self, variant, *args, **kwargs):
        from . import ssm
        return ssm.ssm_step_pallas(*args, **kwargs)

    def oracle(self, variant, x, Bm, Cm, dt, A, state, ids, n):
        from ..models.granite_hybrid import by_group, ssm_step
        if Bm.ndim == 3:                 # a B and a C a group
            return by_group(ssm_step, Bm.shape[1])(x, Bm, Cm, dt, A, state)
        return ssm_step(x, Bm, Cm, dt, A, state)


class GdnStepOp(KernelOp):
    """The gated delta rule of a decode step (models/qwen3_next.py
    `gdn_mix`, one token a slot).  Pallas = kernels/ssm.py's walk of the
    step's live slots over this recurrence: only their state is read and
    written, in place (kernels/gdn.py); oracle = `delta_step`, every
    slot's state through the same expression, a slot that is not live
    under g = 0 and beta = 0.  The shape rule looks at the state's four
    sizes (`kernels/gdn.py::gdn_step_info`)."""

    NAME = "gdn_step"

    def auto_supports(self, variant, info):
        if not info:
            return True, ""
        from .ssm import head_tile

        dk, dv, item = (int(info[k]) for k in ("key_dim", "value_dim",
                                               "itemsize"))
        if item != 4 or dk % 128 or dv != dk:
            return False, (f"a head's state of {dk} x {dv} values of {item} "
                           f"bytes is not square whole 128-lane tiles of "
                           f"float32")
        th = head_tile(int(info["heads"]), dk, dv)
        if not th or th % 8:
            return False, (f"the {th} heads of {dk} x {dv} float32 a block "
                           f"that fit the kernel's VMEM, read and written "
                           f"and double-buffered, are not whole tiles of 8 "
                           f"rows")
        return True, ""

    def pallas(self, variant, *args, **kwargs):
        from . import gdn
        return gdn.gdn_step_pallas(*args, **kwargs)

    def oracle(self, variant, q, k, v, g, beta, state, ids, n):
        from ..models.qwen3_next import delta_step
        return delta_step(q, k, v, g, beta, state)


KERNEL_OPS: Dict[str, KernelOp] = {
    op.NAME: op for op in (FlashAttentionOp(), SparseAttentionOp(),
                           PagedAttentionOp(), GroupedAttentionOp(),
                           LatentAttentionOp(), MaskedLatentAttentionOp(),
                           EvaAttentionOp(),
                           QuantCodecOp(), MoEDispatchOp(),
                           TouchedExpertsOp(), GroupedExpertsOp(),
                           SsmStepOp(), GdnStepOp())
}


def get_kernel(name: str) -> KernelOp:
    if name not in KERNEL_OPS:
        raise ValueError(
            f"unknown kernel op {name!r}; valid ops: "
            f"{sorted(KERNEL_OPS)}")
    return KERNEL_OPS[name]


# ---------------------------------------------------------------------------
# the scoped override (tests)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """What `with kernel_config(...)` holds while it is open.  The
    default-constructed config is what every call outside one sees:
    auto-probe per op, no interpret escape."""

    impl: str = "auto"                 # default for every op
    ops: Mapping[str, str] = dataclasses.field(default_factory=dict)
    interpret: bool = False            # allow forced pallas off-TPU

    def impl_for(self, name: str) -> str:
        return self.ops.get(name, self.impl)


def _impl_value(key: str, v) -> str:
    v = str(v).lower()
    v = _IMPL_ALIASES.get(v, v)
    if v not in KERNEL_IMPLS:
        raise ValueError(
            f"kernels.{key} must be one of {KERNEL_IMPLS}, got {v!r}")
    return v


_KERNEL_CONFIG = KernelConfig()


def get_kernel_config() -> KernelConfig:
    return _KERNEL_CONFIG


@contextlib.contextmanager
def kernel_config(impl: str = "auto", ops: Optional[Mapping] = None,
                  interpret: bool = False):
    """Scoped override for tests:
    `with kernel_config(impl="jnp"): ...` or
    `with kernel_config(ops={"quant_codec": "pallas"}, interpret=True)`.
    An unknown op name or impl value raises here, naming the valid set,
    never inside a traced program.  Selection is read at TRACE time: the
    scope decides for programs traced inside it, never for cached ones."""
    global _KERNEL_CONFIG
    if not isinstance(interpret, bool):
        raise ValueError(
            f"kernels.interpret must be a bool, got {interpret!r}")
    pins = {}
    for name, v in dict(ops or {}).items():
        get_kernel(name)
        pins[name] = _impl_value(f"ops.{name}", v)
    cfg = KernelConfig(impl=_impl_value("impl", impl), ops=pins,
                       interpret=interpret)
    prev, _KERNEL_CONFIG = _KERNEL_CONFIG, cfg
    try:
        yield cfg
    finally:
        _KERNEL_CONFIG = prev


# ---------------------------------------------------------------------------
# resolution + dispatch
# ---------------------------------------------------------------------------


def resolve_impl(name: str, variant: str = "default",
                 impl: Optional[str] = None, interpret_ok: bool = False,
                 info: Optional[Mapping] = None) -> str:
    """-> the concrete "pallas" | "jnp" this call will run (raises on
    an unsatisfiable forced pallas; see module docstring)."""
    op = get_kernel(name)
    op.check_variant(variant)
    cfg = get_kernel_config()
    choice = impl if impl is not None else cfg.impl_for(name)
    choice = _IMPL_ALIASES.get(str(choice).lower(), str(choice).lower())
    if choice not in KERNEL_IMPLS:
        raise ValueError(
            f"kernels.{name}: impl must be one of {KERNEL_IMPLS}, "
            f"got {choice!r}")
    supported, why = op.auto_supports(variant, info)
    if supported and _on_tpu():
        why = op.unpartitionable()
        supported = not why
    if choice == "pallas":
        if _on_tpu():
            # native: nothing the chip's compiler refuses may reach it
            if not op.is_compatible():
                why = op.compatibility_message()
            if why:
                raise RuntimeError(
                    f"kernels.{name}: impl='pallas' forced but {why}; "
                    f"use impl='auto' for the jnp oracle")
        elif not (interpret_ok or cfg.interpret):
            raise RuntimeError(
                f"kernels.{name}: impl='pallas' forced but "
                f"{op.compatibility_message()}; use impl='auto' for the "
                f"jnp fallback, or `with kernel_config(interpret=True)` "
                f"to run the kernel under the Pallas interpreter (tests)")
        return "pallas"
    if choice == "jnp":
        return "jnp"
    # auto: the kernel where it runs natively and the shape rule takes
    # the call
    return "pallas" if op.is_compatible() and supported else "jnp"


def dispatch(name: str, *args, variant: str = "default",
             impl: Optional[str] = None, interpret_ok: bool = False,
             info: Optional[Mapping] = None, **kwargs):
    """Run op `name` through the registry's selection contract.

    Bumps `kernel.dispatches` / `kernel.fallbacks` at trace time (the
    `dist.*` once-per-compiled-program convention).  What it calls is
    traced under the scope `kernel.<name>` (Pallas) or `oracle.<name>`:
    the one place that names every Mosaic call of a compiled program,
    which a device trace otherwise shows as `tpu_custom_call` all alike
    (monitor/tracing.py `program_scopes`)."""
    op = get_kernel(name)
    chosen = resolve_impl(name, variant, impl=impl,
                          interpret_ok=interpret_ok, info=info)
    COUNTERS.add("kernel.dispatches" if chosen == "pallas"
                 else "kernel.fallbacks")
    scope, run = ("kernel", op.pallas) if chosen == "pallas" else \
        ("oracle", op.oracle)
    with jax.named_scope(f"{scope}.{name}"):
        return run(variant, *args, **kwargs)


def probe_report():
    """[(name, verdict, reason)] for every registered op — verdict is
    "pallas" or "jnp-fallback" with the decline reason (ds_report's
    Kernels section; reason is "" when pallas is selected)."""
    rows = []
    for name in sorted(KERNEL_OPS):
        op = KERNEL_OPS[name]
        if op.is_compatible():
            rows.append((name, "pallas", ""))
        else:
            rows.append((name, "jnp-fallback", op.compatibility_message()))
    return rows
