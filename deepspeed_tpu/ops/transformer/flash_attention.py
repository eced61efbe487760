"""Flash attention as Pallas TPU kernels (forward + backward).

TPU-native replacement for the reference's attention kernel chain
(/root/reference/csrc/transformer/ds_transformer_cuda.cpp:147-295: QKV
strided-batch cuBLAS GEMMs + softmax_kernels.cu + dropout): instead of
materialising the [S, S] score matrix in HBM, each (batch·head, q-block)
program streams k/v blocks through VMEM with an online-softmax accumulator,
so HBM traffic is O(S·D) and the MXU sees dense 128×128 tiles.

Layout: kernels operate on [BH, S, D]; the public entry accepts BSHD.
Backward is the standard flash recomputation: forward saves only
out + logsumexp; dq and dk/dv kernels re-form each score block on the fly.

Grid iteration relies on the TPU's sequential innermost grid dimension:
(bh, q_block) are parallel, the k-block sweep is `ARBITRARY` so the VMEM
scratch accumulators persist across it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas_backend

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30  # large-negative instead of -inf: keeps masked rows NaN-free


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


def _keep_mask(seed, bh, q0, k0, bq, bk, rate):
    """fp32 {0, 1/keep} matrix for the (bq, bk) tile at rows q0+, cols k0+.

    E[mask] = 1, so attention stays unbiased (inverted-dropout
    scaling)."""
    u = jnp.uint32
    qi = q0.astype(u) + jax.lax.broadcasted_iota(u, (bq, bk), 0)
    ki = k0.astype(u) + jax.lax.broadcasted_iota(u, (bq, bk), 1)
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


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, scale, causal, bq,
                bk, nk, rate, has_bias):
    if has_bias:
        kb_ref, o_ref, lse_ref, acc, m_s, l_s = rest
    else:
        o_ref, lse_ref, acc, m_s, l_s = rest
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    live = (ki * bk <= qi * bq + bq - 1) if causal else (ki >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qidx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kidx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qidx >= kidx, s, NEG_INF)
        if has_bias:
            s = s + kb_ref[...]  # (1, bk) per-key additive bias, row-bcast
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if has_bias:
            # a fully-masked tile leaves m_new at ~NEG_INF, where
            # exp(s - m_new) = 1 for every masked entry — zero them
            # explicitly (the causal-only path never hits this: the
            # diagonal tile always has a live entry per row)
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        # the softmax denominator accumulates the UNdropped p (dropout acts
        # on normalized probabilities); only the value accumulation sees the
        # dropped, 1/keep-rescaled probabilities
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if rate > 0.0:
            p = p * _keep_mask(seed_ref[0], bh + seed_ref[1],
                               qi * bq, ki * bk, bq, bk, rate)
        acc[:] = acc[:] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_s[:, :1] = m_new
        l_s[:, :1] = l_new

    last = (ki == qi * bq // bk + (bq - 1) // bk) if causal else (ki == nk - 1)

    @pl.when(last)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        # lse carries a broadcast 128-lane trailing dim (TPU tiling: the
        # lane dimension must be 128; same layout as jax's in-tree kernel)
        lse_ref[0] = jnp.broadcast_to(m_s[:, :1] + jnp.log(safe_l),
                                      (bq, 128))


def _fwd(q, k, v, seed, kb, causal, scale, bq, bk, rate, n_heads):
    BH, S, D = q.shape
    Sk = k.shape[1]
    nq, nk = S // bq, Sk // bk
    has_bias = kb is not None
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk, rate=rate,
                               has_bias=has_bias)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
    ]
    operands = [seed, q, k, v]
    if has_bias:
        # [B, Sk] per-key bias; BH programs map back to batch b // H
        in_specs.append(
            pl.BlockSpec((1, bk), lambda b, i, j: (b // n_heads, j)))
        operands.append(kb)
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=pallas_backend.interpret(),
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               *rest, scale, causal, bq, bk, nk, rate, has_bias):
    if has_bias:
        kb_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (ki * bk <= qi * bq + bq - 1) if causal else (ki >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qidx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kidx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qidx >= kidx, s, NEG_INF)
        if has_bias:
            s = s + kb_ref[...]
        p = jnp.exp(s - lse_ref[0][:, :1])
        if has_bias:
            # fully-masked rows carry lse ≈ NEG_INF; exp(s - lse) would
            # resurrect masked entries — zero them like the forward does
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            # dS = P ∘ (mask/keep ∘ dPd − delta); delta = rowsum(dO∘O)
            # equals rowsum(Pd∘dPd), so the no-dropout delta trick holds
            dp = dp * _keep_mask(seed_ref[0], bh + seed_ref[1],
                                 qi * bq, ki * bk, bq, bk, rate)
        ds = p * (dp - delta_ref[0][:, :1])
        dq_acc[:] += scale * jnp.dot(ds.astype(k_ref.dtype), k_ref[0],
                                     preferred_element_type=jnp.float32)

    last = (ki == qi * bq // bk + (bq - 1) // bk) if causal else (ki == nk - 1)

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *rest, scale, causal, bq, bk, nq, rate, has_bias):
    if has_bias:
        kb_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = (qi * bq + bq - 1 >= ki * bk) if causal else (qi >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qidx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kidx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qidx >= kidx, s, NEG_INF)
        if has_bias:
            s = s + kb_ref[...]
        p = jnp.exp(s - lse_ref[0][:, :1])              # (bq, bk)
        if has_bias:
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        do = do_ref[0].astype(jnp.float32)             # (bq, D)
        if rate > 0.0:
            # same (seed, bh, global q, global k) hash as the forward —
            # this kernel's grid swaps (ki, qi) but the mask arguments
            # stay in global-index order, so the tiles agree
            mask = _keep_mask(seed_ref[0], bh + seed_ref[1],
                              qi * bq, ki * bk, bq, bk, rate)
            pd = p * mask
            dp_scale = mask
        else:
            pd = p
            dp_scale = None
        dv_acc[:] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # Pd^T @ do
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dp_scale is not None:
            dp = dp * dp_scale
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[:] += scale * jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # ds^T @ q (unscaled q)
    last = qi == nq - 1

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, scale, bq, bk, rate, n_heads, res, dout):
    q, k, v, seed, kb, out, lse = res
    BH, S, D = q.shape
    Sk = k.shape[1]
    nq, nk = S // bq, Sk // bk
    has_bias = kb is not None
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # (BH, S)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    dq_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
    ]
    dq_operands = [seed, q, k, v, dout, lse, delta]
    if has_bias:
        dq_specs.append(
            pl.BlockSpec((1, bk), lambda b, i, j: (b // n_heads, j)))
        dq_operands.append(kb)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, rate=rate,
                          has_bias=has_bias),
        grid=(BH, nq, nk),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=pallas_backend.interpret(),
    )(*dq_operands)

    dkv_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0)),
    ]
    dkv_operands = [seed, q, k, v, dout, lse, delta]
    if has_bias:
        dkv_specs.append(
            pl.BlockSpec((1, bk), lambda b, j, i: (b // n_heads, j)))
        dkv_operands.append(kb)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, rate=rate,
                          has_bias=has_bias),
        grid=(BH, nk, nq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
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
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    dropout_rate: float = 0.0,
                    dropout_rng=None,
                    key_bias=None,
                    bh_offset=0):
    """Flash attention over [B, S, H, D] inputs (BSHD), causal or full.

    Requires S % block_q == 0 and S_k % block_k == 0 (the dispatcher in
    attention.py falls back to XLA otherwise).

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
    if S % block_q or Sk % block_k:
        raise ValueError(f"seq lens ({S},{Sk}) not divisible by blocks "
                         f"({block_q},{block_k})")
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    scale = (D ** -0.5) if scale is None else scale
    seed, rate = derive_seed(dropout_rate, dropout_rng)
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
