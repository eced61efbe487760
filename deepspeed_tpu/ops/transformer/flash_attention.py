"""Flash attention as Pallas TPU kernels (forward + backward).

TPU-native replacement for the reference's attention kernel chain
(/root/reference/csrc/transformer/ds_transformer_cuda.cpp:147-295: QKV
strided-batch cuBLAS GEMMs + softmax_kernels.cu + dropout): instead of
materialising the [S, S] score matrix in HBM, each (batch·head, q-block)
program keeps a head's K and V resident in VMEM and walks their key
blocks in a loop INSIDE the kernel with an online-softmax accumulator,
so HBM traffic is O(S·D), K/V are fetched once per head and not once
per tile, and a causal walk stops at the diagonal.

Tile schedule (`flash_blocks`, one choice from what the call can see):
a program holds `block_q` query rows and up to `_RESIDENT_BYTES` of
K/V rows (the whole sequence below ~8k keys; a "major" block of it
beyond, walked by the innermost ARBITRARY grid axis whose dead causal
blocks repeat the previous block index and so fetch nothing).  The
loop's tile is (block_q, block_k); only tiles the diagonal crosses
build a mask.  Every product takes its tiles in the INPUT dtype with
fp32 accumulation (bf16 products are exact in fp32; fp32 inputs stay
fp32 products); softmax statistics and accumulators are fp32.

Layout: kernels operate on [BH, S, D]; the public entry accepts BSHD.
Backward is the standard flash recomputation: forward saves only
out + a compact [BH, S] fp32 logsumexp; the dq kernel mirrors the
forward's walk, the dk/dv kernel holds a key block, keeps Q/dO
resident and walks query blocks from the diagonal down on the
TRANSPOSED score tile (keys on sublanes), so logsumexp and delta
broadcast as lane-dense rows.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...monitor.counters import COUNTERS
from .. import pallas_backend

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked rows NaN-free
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract both operands' last dim

# K/V (or Q/dO in the dk/dv kernel) rows held resident per program: two
# operands, double-buffered by the pipeline.  8 MiB is a sixteenth of a
# v5e core's VMEM and holds 16k keys of 64 bf16 lanes.
_RESIDENT_BYTES = 8 * 2 ** 20
# Scoped VMEM the kernels may use, a quarter of a v5e core's 128 MiB.  A
# (512, 512) fp32 score tile lives with its exp, mask, hash and cast
# temporaries (1 MiB each) beside the resident rows; Mosaic's default
# of 16 MiB is what the chip refused PR 22's fused-CE blocks under
# although the described-v5e compile had passed them, so the room is
# asked for and not assumed (tests/test_tpu_compile.py compiles the
# shapes; chip_smoke.py and the benchmark run them).
_VMEM_LIMIT_BYTES = 32 * 2 ** 20


# ---------------------------------------------------------------------------
# probability dropout
# ---------------------------------------------------------------------------
# The reference's attention core applies dropout to the softmax
# probabilities inside the fused kernel (csrc/transformer/dropout_kernels.cu
# via ds_transformer_cuda.cpp). Flash kernels keep probabilities implicit,
# so the mask is REGENERATED tile-by-tile — in the forward and in both
# backward kernels — from (seed, batch·head, global q idx, global k idx)
# with a counter-based integer hash. Pure uint32 arithmetic: identical
# values under the Pallas interpreter (CPU tests) and Mosaic (TPU), and no
# hardware-PRNG state to thread across grid programs. The hash is over
# GLOBAL indices, so the mask is invariant to block-size tuning.

def fmix32(h):
    """THE murmur3-style finalizer — one definition for every hash mask
    (in-kernel tile masks here and in flash_sparse.py, activation
    dropout in dropout.py). Changing the mixing changes which elements
    drop everywhere at once, never in one site only."""
    u = jnp.uint32
    h = h ^ (h >> 15)
    h = h * u(0x2C1B3C6D)
    h = h ^ (h >> 12)
    h = h * u(0x297A2D39)
    h = h ^ (h >> 15)
    return h


def keep_threshold(rate) -> "jnp.uint32":
    """uint32 threshold: keep iff hash < keep·2^32."""
    return jnp.uint32(min(0xFFFFFFFF, int((1.0 - rate) * 4294967296.0)))


def _keep_mask(seed, bh, q0, k0, bq, bk, rate, transposed=False):
    """fp32 {0, 1/keep} matrix for the (bq, bk) tile at rows q0+, cols k0+
    — or, `transposed`, the same tile as (bk, bq) with keys on rows.

    E[mask] = 1, so attention stays unbiased (inverted-dropout
    scaling)."""
    u = jnp.uint32
    shape, qdim, kdim = ((bk, bq), 1, 0) if transposed else ((bq, bk), 0, 1)
    qi = q0.astype(u) + jax.lax.broadcasted_iota(u, shape, qdim)
    ki = k0.astype(u) + jax.lax.broadcasted_iota(u, shape, kdim)
    h = fmix32((seed.astype(u) * u(0x9E3779B1))
               ^ (bh.astype(u) * u(0x7FEB352D))
               ^ (qi * u(0x85EBCA6B)) ^ (ki * u(0xC2B2AE35)))
    return (h < keep_threshold(rate)).astype(jnp.float32) * \
        (1.0 / (1.0 - rate))


def derive_seed(dropout_rate, dropout_rng):
    """(seed array, static rate) for the dropout kernels — ONE definition,
    shared with the sparse flash kernel: the hash-mask contract depends on
    identical seed derivation everywhere."""
    if dropout_rate > 0.0 and dropout_rng is not None:
        seed = jax.random.randint(dropout_rng, (1,), 0,
                                  jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
        return seed, float(dropout_rate)
    return jnp.zeros((1,), jnp.int32), 0.0


def _compiler_params(vmem_limit_bytes=None):
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
        vmem_limit_bytes=vmem_limit_bytes)


# ---------------------------------------------------------------------------
# tile schedule
# ---------------------------------------------------------------------------

def _largest_block(n: int, cap: int) -> int:
    """Largest multiple of 128 that divides n and is at most cap (128
    when none does: the caller's divisibility check then refuses n)."""
    return max((b for b in range(_LANES, min(n, cap) + 1, _LANES)
                if n % b == 0), default=_LANES)


def flash_blocks(S: int, Sk: int) -> Tuple[int, int]:
    """The (block_q, block_k) score tile for a call of these lengths.

    One algorithm wants different tiles by shape, so this is a function
    of what the call can see and not an option.  Measured on a v5e
    (PERF.md §6, PR 27), at the GPT-2 xl training shape and at
    BERT-large's seq 512 with key bias and dropout: the kernels are
    bound by the per-tile softmax on the VPU and by per-program
    overheads, both of which fall with the tile's area until the causal
    diagonal's wasted half-tiles (work x (1 + 1/nq)) and the
    forward's per-tile rescale push back; (512, 512) won both, by 3 %
    over (1024, 1024) and 11 % over (256, 512), 3.2x over (128, 128).
    Shorter or oddly sized sequences take the largest multiple of 128
    that divides them.  Head size, dtype, mask, bias and dropout change
    the tile's temporaries and the resident rows (`_resident_rows`),
    not the winner among the tiles that fit `_VMEM_LIMIT_BYTES`
    (tests/test_tpu_compile.py compiles them at this tile)."""
    return _largest_block(S, 512), _largest_block(Sk, 512)


def _resident_rows(n: int, blk: int, D: int, itemsize: int) -> int:
    """Rows of the walked operands one program keeps in VMEM: the
    largest multiple of `blk` dividing n inside `_RESIDENT_BYTES`
    (two operands, two pipeline buffers each)."""
    cap = max(blk, _RESIDENT_BYTES // (4 * D * itemsize))
    return max(m for m in range(blk, n + 1, blk)
               if n % m == 0 and (m <= cap or m == blk))


def _row_to_col(row):
    """(1, n) -> (n, 1) through a full-vreg transpose (the XLU's)."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, n)))[:, :1]


def _col_to_row(col):
    """(n, 1) -> (1, n)."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1]


def _walk(tile, lo, mid, hi, masked_first):
    """Run `tile(t, masked)` over [lo, mid) and [mid, hi): one range
    masked, the other not.  Two loops, so only tiles the causal
    diagonal crosses pay for the mask's iotas and select."""
    def run(a, b, masked):
        if isinstance(a, int) and isinstance(b, int) and a == b:
            return  # statically empty: trace nothing

        def body(t, carry):
            tile(t, masked)
            return carry
        jax.lax.fori_loop(a, b, body, 0)

    run(lo, mid, masked_first)
    run(mid, hi, not masked_first)


def _key_walk(tile, causal, d, bq, bk, nb):
    """The forward's and dq's walk over the nb resident key tiles of a
    query block whose first row sits d keys past the first resident
    key: tile t needs no mask while its last key is at or before that
    row, and is dead once its first key is past the block's last row."""
    if not causal:
        return _walk(tile, 0, nb, nb, masked_first=False)
    n_full = jnp.minimum(jnp.maximum(d + 1, 0) // bk, nb)
    n_live = jnp.minimum(jnp.maximum(d + bq - 1 + bk, 0) // bk, nb)
    _walk(tile, 0, n_full, n_live, masked_first=False)


def _scores(a, b, a0, b0, *, scale, fold, masked, q_on_rows):
    """fp32 score tile a·bᵀ in the operands' dtype (rows from a at
    global index a0, columns from b at b0), scaled unless `fold` put
    the scale into an operand, causally masked when the walk says the
    diagonal crosses it: a query sees keys at or before it."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if not fold:
        s = s * scale
    if masked:
        rows = a0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = b0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols if q_on_rows else cols >= rows,
                      s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, scale, fold, causal,
                off, bq, bk, bkm, nkm, rate, has_bias):
    if has_bias:
        kb_ref, o_ref, lse_ref, acc, m_s, l_s = rest
    else:
        o_ref, lse_ref, acc, m_s, l_s = rest
    bh = pl.program_id(0)
    q0 = pl.program_id(1) * bq
    kj = pl.program_id(2)
    km0 = kj * bkm
    nb = bkm // bk

    @pl.when(kj == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0]
    if fold:  # a power-of-two scale is exact in every float dtype
        q = q * scale

    def tile(t, masked):
        k0 = pl.multiple_of(t * bk, bk)
        k = k_ref[0, pl.ds(k0, bk), :]
        v = v_ref[0, pl.ds(k0, bk), :]
        s = _scores(q, k, q0 + off, km0 + k0, scale=scale, fold=fold,
                    masked=masked, q_on_rows=True)
        if has_bias:
            s = s + kb_ref[0, t]  # (1, bk) per-key additive bias, row-bcast
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if has_bias or (masked and off < 0):
            # a fully-masked tile leaves m_new at ~NEG_INF, where
            # exp(s - m_new) = 1 for every masked entry — zero them
            # explicitly (the plain causal path never hits this: the
            # walk starts at key 0, which every row may see)
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        # the softmax denominator accumulates the UNdropped p (dropout acts
        # on normalized probabilities); only the value accumulation sees the
        # dropped, 1/keep-rescaled probabilities
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        if rate > 0.0:
            p = p * _keep_mask(seed_ref[0], bh + seed_ref[1],
                               q0, km0 + k0, bq, bk, rate)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_s[...] = m_new

    _key_walk(tile, causal, q0 + off - km0, bq, bk, nb)

    @pl.when(kj == nkm - 1)
    def _finish():
        l = l_s[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = _col_to_row(m_s[...] + jnp.log(safe_l))


def _kv_major_index(causal, nkm, bq, off, bkm):
    """Index of the resident K/V major block at grid step (i, j).  A
    causally dead major block repeats the last live one's index: the
    pipeline sees no change and fetches nothing."""
    if not causal or nkm == 1:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(
        j, jnp.maximum(i * bq + off + bq - 1, 0) // bkm)


def _fwd(q, k, v, seed, kb, causal, scale, bq, bk, rate, n_heads):
    BH, S, D = q.shape
    Sk = k.shape[1]
    off = Sk - S  # causal rows are end-aligned, as in xla_attention
    bkm = _resident_rows(Sk, bk, D, q.dtype.itemsize)
    nq, nkm = S // bq, Sk // bkm
    has_bias = kb is not None
    # which schedule a run ran, per traced call (as kernel.dispatches)
    COUNTERS.add(f"kernel.flash.blocks.{bq}x{bk}.walk{bkm}")
    kernel = functools.partial(
        _fwd_kernel, scale=scale, fold=_is_pow2(scale), causal=causal,
        off=off, bq=bq, bk=bk, bkm=bkm, nkm=nkm, rate=rate,
        has_bias=has_bias)
    kj = _kv_major_index(causal, nkm, bq, off, bkm)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bkm, D), lambda b, i, j: (b, kj(i, j), 0)),
        pl.BlockSpec((1, bkm, D), lambda b, i, j: (b, kj(i, j), 0)),
    ]
    operands = [seed, q, k, v]
    if has_bias:
        # [B, Sk] per-key bias as [B, Sk/bk, 1, bk] rows: the walk picks
        # tile t on a leading dim; BH programs map back to batch b // H
        in_specs.append(pl.BlockSpec(
            (1, bkm // bk, 1, bk),
            lambda b, i, j: (b // n_heads, kj(i, j), 0, 0)))
        operands.append(kb.reshape(-1, Sk // bk, 1, bk))
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nkm),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, nq, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(_VMEM_LIMIT_BYTES),
        interpret=pallas_backend.interpret(),
    )(*operands)
    return out, lse.reshape(BH, S)


def _is_pow2(x: float) -> bool:
    return x > 0 and math.frexp(x)[0] == 0.5


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               *rest, scale, fold, causal, off, bq, bk, bkm, nkm, rate,
               has_bias):
    if has_bias:
        kb_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
    bh = pl.program_id(0)
    q0 = pl.program_id(1) * bq
    kj = pl.program_id(2)
    km0 = kj * bkm
    nb = bkm // bk

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0]
    if fold:
        q = q * scale
    do = do_ref[0]
    lse = _row_to_col(lse_ref[0, 0])
    delta = _row_to_col(delta_ref[0, 0])

    def tile(t, masked):
        k0 = pl.multiple_of(t * bk, bk)
        k = k_ref[0, pl.ds(k0, bk), :]
        v = v_ref[0, pl.ds(k0, bk), :]
        s = _scores(q, k, q0 + off, km0 + k0, scale=scale, fold=fold,
                    masked=masked, q_on_rows=True)
        if has_bias:
            s = s + kb_ref[0, t]
        p = jnp.exp(s - lse)
        if has_bias or (masked and off < 0):
            # fully-masked rows carry lse ≈ NEG_INF; exp(s - lse) would
            # resurrect masked entries — zero them like the forward does
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            # dS = P ∘ (mask/keep ∘ dPd − delta); delta = rowsum(dO∘O)
            # equals rowsum(Pd∘dPd), so the no-dropout delta trick holds
            dp = dp * _keep_mask(seed_ref[0], bh + seed_ref[1],
                                 q0, km0 + k0, bq, bk, rate)
        ds = p * (dp - delta)
        dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    _key_walk(tile, causal, q0 + off - km0, bq, bk, nb)

    @pl.when(kj == nkm - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *rest, scale, fold, causal, off, bq, bk, bqm, nqm, rate,
                has_bias):
    """One key block, Q/dO resident, query tiles walked on the TRANSPOSED
    score tile sT = K·Qᵀ (bk, bq): every product is a plain or an
    a·bᵀ matmul, and lse/delta broadcast as (1, bq) rows."""
    if has_bias:
        kb_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    bh = pl.program_id(0)
    k0 = pl.program_id(1) * bk
    qj = pl.program_id(2)
    qm0 = qj * bqm
    nb = bqm // bq

    @pl.when(qj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k = k_ref[0]
    if fold:
        k = k * scale
    v = v_ref[0]
    if has_bias:
        kb_col = _row_to_col(kb_ref[0, 0])

    def tile(t, masked):
        r0 = pl.multiple_of(t * bq, bq)
        q = q_ref[0, pl.ds(r0, bq), :]
        do = do_ref[0, pl.ds(r0, bq), :]
        sT = _scores(k, q, k0, qm0 + r0 + off, scale=scale, fold=fold,
                     masked=masked, q_on_rows=False)
        if has_bias:
            sT = sT + kb_col
        pT = jnp.exp(sT - lse_ref[0, t])                 # (bk, bq)
        if has_bias or (masked and off < 0):
            pT = jnp.where(sT <= NEG_INF * 0.5, 0.0, pT)
        dpT = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        pdT = pT
        if rate > 0.0:
            # same (seed, bh, global q, global k) hash as the forward —
            # the tile is transposed but the mask arguments stay in
            # global-index order, so the tiles agree
            mask = _keep_mask(seed_ref[0], bh + seed_ref[1], qm0 + r0, k0,
                              bq, bk, rate, transposed=True)
            pdT = pT * mask
            dpT = dpT * mask
        dv_acc[...] += jnp.dot(pdT.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dsT = pT * (dpT - delta_ref[0, t])
        dk_acc[...] += jnp.dot(dsT.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    if causal:
        # query tile t (rows qm0 + t*bq ...) is dead while its last row
        # is before the block's first key, and needs no mask once its
        # first row is at or past the block's last key
        e = k0 - off - qm0
        t_lo = jnp.minimum(jnp.maximum(e, 0) // bq, nb)
        t_full = jnp.minimum(jnp.maximum(e + bk + bq - 2, 0) // bq, nb)
        _walk(tile, t_lo, t_full, nb, masked_first=True)
    else:
        _walk(tile, 0, 0, nb, masked_first=True)

    @pl.when(qj == nqm - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(causal, scale, bq, bk, rate, n_heads, res, dout):
    q, k, v, seed, kb, out, lse = res
    BH, S, D = q.shape
    Sk = k.shape[1]
    off = Sk - S
    itemsize = q.dtype.itemsize
    bkm = _resident_rows(Sk, bk, D, itemsize)
    bqm = _resident_rows(S, bq, D, itemsize)
    nq, nk, nkm, nqm = S // bq, Sk // bk, Sk // bkm, S // bqm
    has_bias = kb is not None
    static = dict(scale=scale, fold=_is_pow2(scale), causal=causal, off=off,
                  bq=bq, bk=bk, rate=rate, has_bias=has_bias)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # (BH, S)
    # lane-dense rows, one (1, bq) slab per query tile on a leading dim
    lse = lse.reshape(BH, nq, 1, bq)
    delta = delta.reshape(BH, nq, 1, bq)
    if has_bias:
        kb = kb.reshape(-1, nk, 1, bk)

    kj = _kv_major_index(causal, nkm, bq, off, bkm)
    dq_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bkm, D), lambda b, i, j: (b, kj(i, j), 0)),
        pl.BlockSpec((1, bkm, D), lambda b, i, j: (b, kj(i, j), 0)),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, 1, bq), lambda b, i, j: (b, i, 0, 0)),
        pl.BlockSpec((1, 1, 1, bq), lambda b, i, j: (b, i, 0, 0)),
    ]
    dq_operands = [seed, q, k, v, dout, lse, delta]
    if has_bias:
        dq_specs.append(pl.BlockSpec(
            (1, bkm // bk, 1, bk),
            lambda b, i, j: (b // n_heads, kj(i, j), 0, 0)))
        dq_operands.append(kb)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bkm=bkm, nkm=nkm, **static),
        grid=(BH, nq, nkm),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(_VMEM_LIMIT_BYTES),
        interpret=pallas_backend.interpret(),
    )(*dq_operands)

    if causal and nqm > 1:  # dead Q/dO major blocks repeat the first live
        qj = lambda i, j: jnp.minimum(
            jnp.maximum(j, jnp.maximum(i * bk - off, 0) // bqm), nqm - 1)
    else:
        qj = lambda i, j: j
    dkv_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bqm, D), lambda b, i, j: (b, qj(i, j), 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bqm, D), lambda b, i, j: (b, qj(i, j), 0)),
        pl.BlockSpec((1, bqm // bq, 1, bq),
                     lambda b, i, j: (b, qj(i, j), 0, 0)),
        pl.BlockSpec((1, bqm // bq, 1, bq),
                     lambda b, i, j: (b, qj(i, j), 0, 0)),
    ]
    dkv_operands = [seed, q, k, v, dout, lse, delta]
    if has_bias:
        dkv_specs.append(pl.BlockSpec(
            (1, 1, 1, bk), lambda b, i, j: (b // n_heads, i, 0, 0)))
        dkv_operands.append(kb)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bqm=bqm, nqm=nqm, **static),
        grid=(BH, nk, nqm),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_compiler_params(_VMEM_LIMIT_BYTES),
        interpret=pallas_backend.interpret(),
    )(*dkv_operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (BSHD) with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_bhsd(q, k, v, seed, kb, causal, scale, bq, bk, rate, n_heads):
    out, _ = _fwd(q, k, v, seed, kb, causal, scale, bq, bk, rate, n_heads)
    return out


def _flash_fwd_rule(q, k, v, seed, kb, causal, scale, bq, bk, rate,
                    n_heads):
    out, lse = _fwd(q, k, v, seed, kb, causal, scale, bq, bk, rate, n_heads)
    return out, (q, k, v, seed, kb, out, lse)


def _flash_bwd_rule(causal, scale, bq, bk, rate, n_heads, res, dout):
    return (*_bwd(causal, scale, bq, bk, rate, n_heads, res, dout),
            None, None)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    dropout_rate: float = 0.0,
                    dropout_rng=None,
                    key_bias=None,
                    bh_offset=0):
    """Flash attention over [B, S, H, D] inputs (BSHD), causal or full.

    block_q / block_k are the score tile's rows and columns in all three
    kernels; left None, `flash_blocks` picks them from the call's shape.
    Requires S % block_q == 0 and S_k % block_k == 0 (the dispatcher in
    attention.py falls back to XLA otherwise).  Causal rows are
    end-aligned when S != S_k (row i sees keys up to i + S_k - S), as in
    `xla_attention`.

    dropout_rate > 0 with a dropout_rng applies probability dropout inside
    the kernel (reference: attention-probability dropout in the fused CUDA
    layer, csrc/transformer/dropout_kernels.cu) — the mask is hash-generated
    per tile from a per-call seed, never materialised at [S, S], and
    regenerated identically in the backward kernels.

    key_bias is a per-key additive bias, [B, Sk] or [B, 1, 1, Sk] fp32
    (the BERT padding-mask convention: 0 keep, large-negative masked;
    reference adds it pre-softmax in softmax_kernels.cu). Rows whose keys
    are ALL masked produce zero output (the XLA path's softmax yields a
    uniform don't-care row there instead).

    bh_offset shifts the dropout hash's batch·head coordinate to the
    GLOBAL index: the in-kernel mask hashes (seed, bh, q, k) with bh the
    kernel-local program id, so when the inputs are a shard of a larger
    batch/head space (DP batch shards, Ulysses head shards under
    shard_map) every shard would otherwise draw the IDENTICAL mask
    pattern for its local slots.  Manual-partition callers pass
    `jax.lax.axis_index(axis) * local_BH` (may be traced — it rides the
    SMEM seed operand) and shards become decorrelated while matching
    the unsharded run bit-for-bit.
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    scale = (D ** -0.5) if scale is None else scale
    seed, rate = derive_seed(dropout_rate, dropout_rng)
    auto_q, auto_k = flash_blocks(S, Sk)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    if S % block_q or Sk % block_k:
        raise ValueError(f"seq lens ({S},{Sk}) not divisible by blocks "
                         f"({block_q},{block_k})")
    # seed row 1 carries the global batch·head offset for the hash
    seed = jnp.concatenate(
        [seed, jnp.asarray(bh_offset, jnp.int32).reshape(1)])
    kb = None
    if key_bias is not None:
        kb = jnp.asarray(key_bias, jnp.float32).reshape(-1, Sk)
        kb = jnp.broadcast_to(kb, (B, Sk))
        # clamp so s + bias stays finite (finfo.min would NaN the exp)
        kb = jnp.maximum(kb, NEG_INF)
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], D)
    out = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v), seed, kb, causal,
                      scale, block_q, block_k, rate, H)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
