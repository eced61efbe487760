"""Multi-head attention dispatch — Pallas flash attention on TPU, fused XLA
elsewhere.

Reference: the fused CUDA transformer kernel's attention core
(/root/reference/csrc/transformer/ds_transformer_cuda.cpp:147-295 — QKV
strided-batch GEMM + softmax kernels + dropout). TPU-native design: one
flash-attention Pallas kernel (ops/transformer/flash_attention.py) computes
softmax(QK^T)V in VMEM-resident tiles without materialising the [S, S]
score matrix; off-TPU (and for shapes the kernel doesn't tile) an XLA
einsum path that the compiler fuses.

Shapes follow [batch, seq, heads, head_dim] (BSHD).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .. import pallas_backend

# Below this XLA's fused attention wins.  Measured on a v5e, forward +
# backward of 16k tokens at 25 heads of 64, bf16 (PERF.md §6, PR 27):
# S 256: XLA 1.02 ms, flash 1.51; S 512: XLA 2.43, flash 1.55; S 1024:
# XLA 4.69, flash 1.99.  (384 is not measured and stays with XLA.)
_FLASH_MIN_SEQ = 512


def _on_tpu() -> bool:
    return not pallas_backend.interpret()


_AD_TRACER_NAMES = ("JVPTracer", "LinearizeTracer")


def _is_ad_tracer(x) -> bool:
    """True when x is being differentiated (a JVP/linearize tracer at ANY
    nesting depth).

    The flash kernel's VJP returns no cotangent for its key-bias operand,
    so a bias that itself needs gradients (e.g. a learnable per-key bias)
    must stay on the XLA path; a constant padding mask — even inside jit
    or under grad-w.r.t.-params, where it is an ArrayImpl or a plain
    DynamicJaxprTracer — still takes the kernel.

    Transform stacks WRAP the AD tracer: under vmap(grad(f)) the bias is
    a BatchTracer whose payload is the JVPTracer, so checking only the
    outermost type would silently route a differentiated bias to the
    kernel and return a zero cotangent.  Walk the nesting (BatchTracer
    carries `.val`, JVP/Linearize carry `.primal`) until an AD tracer is
    found or the payload stops being a tracer."""
    from jax.core import Tracer

    for _ in range(32):  # transform stacks are shallow; bound the walk
        if type(x).__name__ in _AD_TRACER_NAMES:
            return True
        if not isinstance(x, Tracer):
            return False
        inner = getattr(x, "val", None)
        if inner is None:
            inner = getattr(x, "primal", None)
        if inner is None or inner is x:
            return False
        x = inner
    return False


def xla_attention(q, k, v, causal=True, bias=None, dropout_rate=0.0,
                  dropout_rng=None, train=False, scale=None):
    """Reference attention in pure XLA. [B,S,H,D] -> [B,S,H,D].

    fp32 softmax regardless of input dtype (parity with the reference's
    softmax kernel which upcasts — csrc/transformer/softmax_kernels.cu).
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    scale = (D ** -0.5) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    if causal:
        qi = jnp.arange(S)[:, None] + (Sk - S)  # offset for cached decoding
        ki = jnp.arange(Sk)[None, :]
        scores = jnp.where(qi >= ki, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    if train and dropout_rate > 0.0 and dropout_rng is not None:
        # counter-hash mask (dropout.py): the [B,H,S,S] probability
        # tensor is the single largest per-element threefry bill in the
        # model — the hash mask costs ~6 fused int ops instead
        from .dropout import hash_dropout

        probs = hash_dropout(probs, dropout_rate, dropout_rng)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def _flash_per_shard(flash, q, k, v, key_bias, dropout_rng, bh_offset,
                     **kw):
    """Call the flash kernel once per shard of the current mesh.

    XLA cannot partition a Mosaic kernel: under `jit` over a mesh of
    more than one device the native lowering raises "Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" (first met on four v5e chips, PR 22; the interpreter
    off-TPU lowers to plain ops and never shows it).  Attention is
    independent per batch row and per head, so the call is made under a
    `shard_map` over every mesh axis that is not manual already: batch
    rows split over the data axes, heads over `model`, everything else
    replicated.  The dropout hash is keyed by the GLOBAL batch*head
    index (`bh_offset`), which a head split cannot express, so with
    dropout active the heads stay whole."""
    from jax.sharding import PartitionSpec as P

    from ...comm.mesh import MODEL_AXIS, peek_mesh

    def call(q, k, v, key_bias, dropout_rng, bh_offset):
        return flash(q, k, v, key_bias=key_bias, dropout_rng=dropout_rng,
                     bh_offset=bh_offset, **kw)

    info = peek_mesh()
    if info is None or not info.auto_axes():
        return call(q, k, v, key_bias, dropout_rng, bh_offset)
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    B, H = q.shape[0], q.shape[2]
    data_axes = tuple(a for a in info.data_axes if a not in manual)
    if B % math.prod(info.axis_size(a) for a in data_axes):
        data_axes = ()
    batch = data_axes or None
    mp = info.axis_size(MODEL_AXIS)
    heads = MODEL_AXIS if (mp > 1 and H % mp == 0 and dropout_rng is None
                           and MODEL_AXIS not in manual) else None
    qkv = P(batch, None, heads, None)
    bias = None if key_bias is None else P(
        batch if key_bias.shape[0] == B else None,
        *([None] * (key_bias.ndim - 1)))

    def body(q, k, v, key_bias, dropout_rng, bh_offset):
        if dropout_rng is not None and data_axes:
            rank = 0
            for a in data_axes:  # outer-major, the mesh's device order
                rank = rank * info.axis_size(a) + jax.lax.axis_index(a)
            bh_offset = bh_offset + rank * (q.shape[0] * q.shape[2])
        return call(q, k, v, key_bias, dropout_rng, bh_offset)

    return jax.shard_map(
        body, mesh=info.mesh,
        in_specs=(qkv, qkv, qkv, bias, P(), P()), out_specs=qkv,
        axis_names=set(info.mesh.axis_names) - manual,
        check_vma=False)(q, k, v, key_bias, dropout_rng,
                         jnp.asarray(bh_offset, jnp.int32))


def multihead_attention(q, k, v, causal: bool = True, impl: str = "auto",
                        bias=None, dropout_rate: float = 0.0,
                        dropout_rng=None, train: bool = False,
                        scale: Optional[float] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        bh_offset=0):
    """Dispatching attention entry point used by the GPT family and the
    DeepSpeedTransformerLayer.

    impl: "auto" (pallas on TPU when tileable), "pallas", "xla".
    The Pallas path applies probability dropout in-kernel (hash-generated
    tile masks, no [S, S] materialisation) and accepts per-key additive
    biases ([B, 1, 1, Sk] — the BERT padding-mask shape) in-kernel too;
    only a full [.., S, Sk] bias (e.g. relative-position) routes to XLA.
    """
    B, S, D = q.shape[0], q.shape[1], q.shape[3]
    Sk = k.shape[1]
    want_dropout = train and dropout_rate > 0.0 and dropout_rng is not None
    key_bias = None
    if bias is not None and getattr(bias, "ndim", 0) == 4 \
            and bias.shape[1] == 1 and bias.shape[2] == 1 \
            and bias.shape[3] == Sk and bias.shape[0] in (1, B) \
            and not _is_ad_tracer(bias):
        key_bias = bias
    use_pallas = False
    if impl == "pallas":
        # the flash kernel carries per-key biases only; honoring a full
        # [.., S, Sk] bias wins over the impl request (silently dropping
        # a mask is numerically wrong)
        use_pallas = bias is None or key_bias is not None
    elif impl == "auto":
        use_pallas = (_on_tpu() and (bias is None or key_bias is not None)
                      and S >= _FLASH_MIN_SEQ and S % 128 == 0
                      and Sk % 128 == 0 and D in (64, 128, 256))
    if use_pallas:
        from .flash_attention import flash_attention, flash_blocks

        auto_q, auto_k = flash_blocks(S, Sk)
        bq, bk = block_q or auto_q, block_k or auto_k
        if S % bq == 0 and Sk % bk == 0:
            return _flash_per_shard(
                flash_attention, q, k, v, key_bias,
                dropout_rng if want_dropout else None, bh_offset,
                causal=causal, scale=scale, block_q=bq, block_k=bk,
                dropout_rate=dropout_rate if want_dropout else 0.0)
        if block_q or block_k:
            # explicit tuning request that cannot tile: say so instead of
            # silently paying the O(S^2) XLA path
            from ...utils.logging import logger

            logger.warning(
                f"flash blocks ({bq},{bk}) do not divide seq lens "
                f"({S},{Sk}); falling back to XLA attention")
    try:
        offset_zero = int(bh_offset) == 0  # any concrete zero is a no-op
    except Exception:  # traced (e.g. axis_index): unknowable at dispatch
        offset_zero = False
    if want_dropout and not offset_zero:
        # the XLA path's dropout has no shard-offset notion — silently
        # dropping it would re-correlate the shard masks the caller is
        # explicitly decorrelating
        raise ValueError(
            "bh_offset is only honored by the flash kernel; this call "
            "dispatched to XLA attention (non-TPU platform, untileable "
            "shapes, a full bias, or a differentiated bias) with dropout "
            "active — use impl='pallas' with tileable shapes, or drop "
            "bh_offset")
    return xla_attention(q, k, v, causal=causal, bias=bias,
                         dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                         train=train, scale=scale)
