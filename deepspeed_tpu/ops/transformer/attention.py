"""Multi-head attention of a training step: the flash kernel or fused XLA,
whichever the kernel registry picks for the call.

Reference: the fused CUDA transformer kernel's attention core
(/root/reference/csrc/transformer/ds_transformer_cuda.cpp:147-295 — QKV
strided-batch GEMM + softmax kernels + dropout). TPU-native design: one
flash-attention Pallas kernel (ops/transformer/flash_attention.py) computes
softmax(QK^T)V in VMEM-resident tiles without materialising the [S, S]
score matrix; off-TPU (and for shapes the kernel doesn't tile) an XLA
einsum path that the compiler fuses.  `multihead_attention` says what
the call looks like (`flash_info`) and `kernels/registry.py` chooses.

Shapes follow [batch, seq, heads, head_dim] (BSHD).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

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


def flash_per_shard(q, k, v, *, bias=None, dropout_rng=None, bh_offset=0,
                    **kw):
    """The registry's `flash_attention` kernel: the flash kernel called
    once per shard of the current mesh.  `bias` is None or per-key
    (`flash_info` says "key").

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
    from .flash_attention import flash_attention

    def call(q, k, v, key_bias, dropout_rng, bh_offset):
        return flash_attention(q, k, v, key_bias=key_bias,
                               dropout_rng=dropout_rng,
                               bh_offset=bh_offset, **kw)

    info = peek_mesh()
    if info is None or not info.auto_axes():
        return call(q, k, v, bias, dropout_rng, bh_offset)
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
    bias_spec = None if bias is None else P(
        batch if bias.shape[0] == B else None,
        *([None] * (bias.ndim - 1)))

    def body(q, k, v, key_bias, dropout_rng, bh_offset):
        if dropout_rng is not None and data_axes:
            rank = 0
            for a in data_axes:  # outer-major, the mesh's device order
                rank = rank * info.axis_size(a) + jax.lax.axis_index(a)
            bh_offset = bh_offset + rank * (q.shape[0] * q.shape[2])
        return call(q, k, v, key_bias, dropout_rng, bh_offset)

    return jax.shard_map(
        body, mesh=info.mesh,
        in_specs=(qkv, qkv, qkv, bias_spec, P(), P()), out_specs=qkv,
        axis_names=set(info.mesh.axis_names) - manual,
        check_vma=False)(q, k, v, bias, dropout_rng,
                         jnp.asarray(bh_offset, jnp.int32))


def xla_oracle(q, k, v, *, bias=None, dropout_rate=0.0, dropout_rng=None,
               bh_offset=0, **kw):
    """The registry's `flash_attention` oracle: `xla_attention` over the
    whole arrays (XLA partitions it), any bias."""
    try:
        offset_zero = int(bh_offset) == 0  # any concrete zero is a no-op
    except Exception:  # traced (e.g. axis_index): unknowable at dispatch
        offset_zero = False
    if dropout_rng is not None and not offset_zero:
        # the XLA path's dropout has no shard-offset notion — silently
        # dropping it would re-correlate the shard masks the caller is
        # explicitly decorrelating
        raise ValueError(
            "bh_offset is only honored by the flash kernel; this call "
            "dispatched to XLA attention (non-TPU platform, untileable "
            "shapes, a full bias, or a differentiated bias) with dropout "
            "active — use impl='pallas' with tileable shapes, or drop "
            "bh_offset")
    return xla_attention(q, k, v, bias=bias, dropout_rate=dropout_rate,
                         dropout_rng=dropout_rng,
                         train=dropout_rng is not None, **kw)


def flash_info(q, k, bias=None) -> dict:
    """What the registry's shape rule may look at (kernels/registry.py,
    `FlashAttentionOp.auto_supports`): the lengths, the head size, and
    whether the bias is one the kernel can add — per key, [B or 1, 1, 1,
    Sk], the BERT padding-mask shape, and not being differentiated."""
    B, Sk = q.shape[0], k.shape[1]
    kind = "none"
    if bias is not None:
        keyed = (getattr(bias, "ndim", 0) == 4
                 and bias.shape[1] == 1 and bias.shape[2] == 1
                 and bias.shape[3] == Sk and bias.shape[0] in (1, B)
                 and not _is_ad_tracer(bias))
        kind = "key" if keyed else "full"
    return {"seq_len": q.shape[1], "kv_len": Sk, "head_dim": q.shape[3],
            "bias": kind}


def multihead_attention(q, k, v, causal: bool = True, impl: str = "auto",
                        bias=None, dropout_rate: float = 0.0,
                        dropout_rng=None, train: bool = False,
                        scale: Optional[float] = None,
                        bh_offset=0):
    """Attention entry point used by the GPT family and the
    DeepSpeedTransformerLayer.

    impl: "auto" (the kernel registry's rule for `flash_attention`),
    "pallas", "xla".
    The Pallas path applies probability dropout in-kernel (hash-generated
    tile masks, no [S, S] materialisation) and accepts per-key additive
    biases ([B, 1, 1, Sk] — the BERT padding-mask shape) in-kernel too;
    only a full [.., S, Sk] bias (e.g. relative-position) routes to XLA.
    """
    from ...kernels import registry

    info = flash_info(q, k, bias)
    if impl == "auto":
        impl = None  # the registry's rule, or the scope a test opened
    if info["bias"] == "full":
        # the flash kernel carries per-key biases only; honoring a full
        # [.., S, Sk] bias wins over the impl request (silently dropping
        # a mask is numerically wrong)
        impl = "xla"
    want_dropout = train and dropout_rate > 0.0 and dropout_rng is not None
    return registry.dispatch(
        "flash_attention", q, k, v, impl=impl, interpret_ok=True,
        info=info, causal=causal, scale=scale, bias=bias,
        dropout_rate=dropout_rate if want_dropout else 0.0,
        dropout_rng=dropout_rng if want_dropout else None,
        bh_offset=bh_offset)
