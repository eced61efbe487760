"""Pallas blockwise int8/int4 quantize/dequantize (op 2: the ZeRO++
qwZ/qgZ wire codec from runtime/comm/quant.py).

ZeRO++'s own finding motivates this op: once the wire shrinks 4-8x, the
CODEC becomes the bottleneck — on TPU the amax/scale/round chain should
run as one VMEM-resident pass per block tile instead of the half-dozen
HBM-roundtripping XLA ops the jnp expression lowers to.

Parity contract: BIT-exact with `quantize_blockwise_ref` /
`dequantize_blockwise_ref`.  The kernels replicate the oracle's op
sequence per tile — subnormal flush, finite-masked amax, fp16-rounded
scale reused as the quantization scale, round/clip, the -qmax-1
non-finite marker — using the same jnp primitives, so interpret-mode
CPU runs produce identical bits (pinned in tier-1) and the int4 nibble
pack/unpack stays in the jnp wrappers (pure bit movement XLA handles
fine; the arithmetic is what the kernel owns).

Layout notes (TPU-native): tiles are `_TILE` = 8 block-rows x `block`
lanes, so `block % 128 == 0` tiles cleanly (the registry's auto
heuristic gates on it; DEFAULT_BLOCK_SIZE = 256 qualifies).  Scales
travel through a 128-lane fp32 broadcast column, already rounded to
fp16 values (`_round_to_f16`); the wrappers cast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import pallas_backend
from ..runtime.comm.quant import (_F32_MIN_NORMAL, qmax,
                                  validate_block_size)

_TILE = 8  # block-rows per grid program (fp32 sublane tile)


def _params(ndims: int):
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL,) * ndims)


def _pad_rows(a, tile: int):
    """Zero-pad leading (row) axis to a whole number of tiles."""
    pad = -a.shape[0] % tile
    if pad:
        a = jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
    return a


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------


def _round_to_f16(x):
    """The fp32 value of `x.astype(float16)` for finite x >= 0, in
    integer ops: Mosaic has no f32 -> f16 pack on this chip (`failed to
    legalize operation 'tpu.pack_subelements'`), so the kernel rounds
    here and the wrapper's cast to fp16 is then exact."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    # fp16 normals keep 10 of 23 mantissa bits: nearest-even on the 13
    # dropped ones (a carry into the exponent is the right answer)
    bits = bits + jnp.uint32(0xFFF) + ((bits >> 13) & jnp.uint32(1))
    normal = jax.lax.bitcast_convert_type(
        bits & jnp.uint32(0xFFFFE000), jnp.float32)
    # below 2**-14 fp16 is subnormal: a fixed quantum of 2**-24
    sub = jnp.round(x * jnp.float32(2.0 ** 24)) * jnp.float32(2.0 ** -24)
    out = jnp.where(x < jnp.float32(2.0 ** -14), sub, normal)
    return jnp.where(out > jnp.float32(65504.0), jnp.float32(jnp.inf), out)


def _quant_kernel(x_ref, codes_ref, scales_ref, *, q):
    # the oracle's encode chain, verbatim per tile (quant.py):
    # flush -> finite amax -> fp16 scale -> inv -> round/clip -> marker
    blocks = x_ref[...]
    blocks = jnp.where(jnp.abs(blocks) < jnp.float32(_F32_MIN_NORMAL),
                       jnp.float32(0.0), blocks)
    finite = jnp.isfinite(blocks)
    amax = jnp.max(jnp.where(finite, jnp.abs(blocks), 0.0),
                   axis=1, keepdims=True)
    eff = _round_to_f16(amax / q)
    inv = jnp.where((eff > 0) & jnp.isfinite(eff), 1.0 / eff, 0.0)
    codes = jnp.clip(jnp.round(blocks * inv), -q, q).astype(jnp.int8)
    codes_ref[...] = jnp.where(finite, codes, jnp.int8(-q - 1))
    scales_ref[...] = jnp.broadcast_to(eff, scales_ref.shape)


def quantize_blockwise_pallas(x, block: int, wire: str = "int8"):
    """Drop-in for `quantize_blockwise_ref`: flat tensor -> (int8 codes
    | packed int4 nibbles, fp16 scales), bit-identical payload."""
    q = qmax(wire)
    block = validate_block_size(block)

    f32 = x.reshape(-1).astype(jnp.float32)
    pad = -f32.shape[0] % block
    if pad:
        f32 = jnp.concatenate([f32, jnp.zeros((pad,), jnp.float32)])
    blocks = f32.reshape(-1, block)
    nb = blocks.shape[0]
    # pad rows to the tile; a zero row encodes deterministically to
    # (codes 0, scale 0) and is sliced back off
    blocks = _pad_rows(blocks, _TILE)
    grid = (blocks.shape[0] // _TILE,)

    codes, scales = pl.pallas_call(
        functools.partial(_quant_kernel, q=q),
        grid=grid,
        in_specs=[pl.BlockSpec((_TILE, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(blocks.shape, jnp.int8),
            jax.ShapeDtypeStruct((blocks.shape[0], 128), jnp.float32),
        ],
        compiler_params=_params(1),
        interpret=pallas_backend.interpret(),
    )(blocks)
    codes = codes[:nb]
    scales = scales[:nb, 0].astype(jnp.float16)

    if q == 127:
        return codes, scales
    u = codes.astype(jnp.uint8) & jnp.uint8(0x0F)
    packed = u[:, 0::2] | (u[:, 1::2] << 4)
    return packed, scales


# ---------------------------------------------------------------------------
# dequantize
# ---------------------------------------------------------------------------


def _dequant_kernel(codes_ref, scales_ref, out_ref, *, marker):
    # compared as fp32: the chip has no int8 vector comparison
    # ("Target does not support this comparison")
    codes = codes_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.where(codes == marker, jnp.float32(jnp.nan),
                             codes * scales_ref[:, :1])


def dequantize_blockwise_pallas(payload, scales, wire: str,
                                n_elems: int):
    """Drop-in for `dequantize_blockwise_ref`: fused codes-x-scale with
    the marker -> NaN reconstruction in-kernel; leading batch dims
    (gathered wires arrive [world, nb, w]) fold into the row axis."""
    q = qmax(wire)
    marker = -q - 1
    lead = payload.shape[:-2]
    if q == 127:
        codes = payload.astype(jnp.int8)
    else:
        lo = (payload & jnp.uint8(0x0F)).astype(jnp.int8)
        hi = ((payload >> 4) & jnp.uint8(0x0F)).astype(jnp.int8)
        lo = jnp.where(lo > 7, lo - 16, lo)
        hi = jnp.where(hi > 7, hi - 16, hi)
        codes = jnp.stack([lo, hi], axis=-1).reshape(
            payload.shape[:-1] + (payload.shape[-1] * 2,))
    block = codes.shape[-1]
    codes = codes.reshape(-1, block)
    nb = codes.shape[0]
    s128 = jnp.broadcast_to(
        scales.astype(jnp.float32).reshape(-1, 1), (nb, 128))
    codes = _pad_rows(codes, _TILE)
    s128 = _pad_rows(s128, _TILE)
    grid = (codes.shape[0] // _TILE,)

    vals = pl.pallas_call(
        functools.partial(_dequant_kernel, marker=marker),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_TILE, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(codes.shape, jnp.float32),
        compiler_params=_params(1),
        interpret=pallas_backend.interpret(),
    )(codes, s128)
    flat = vals[:nb].reshape(lead + (-1,))
    return flat[..., :n_elems]
