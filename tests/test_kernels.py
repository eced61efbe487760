"""The Pallas kernel registry (deepspeed_tpu/kernels/).

* every registered op's Pallas kernel matches its jnp oracle ON CPU
  (the kernel runs under the Pallas interpreter there) — BIT-exact for
  the quant codec (both wires, both directions, non-finite markers
  included) and the MoE dispatch permutation; tolerance-bounded for
  attention and the MoE combine (reduction-order / FMA rounding);
* the choice is a function of what the call can see: backend, the
  op's shape rule over the call's `info`, the mesh, and the op's
  `DS_KERNEL_<OP>` environment switch — for every op, training
  attention included;
* an unknown op name fails where the scoped override is opened, naming
  the registered set, never inside a traced program;
* `impl="pallas"` forced off-TPU raises loudly unless the interpret
  escape is set;
* `kernel.dispatches` / `kernel.fallbacks` count every resolution;
* nothing an engine does changes what a later trace selects.
"""

import io
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.kernels import (KERNEL_OPS, get_kernel_config,
                                   kernel_config, probe_report, registry,
                                   resolve_impl)
from deepspeed_tpu.monitor.counters import COUNTERS

ON_TPU = jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# oracle parity (the correctness contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_quant_codec_parity_bit_exact(wire):
    """The Pallas codec is BIT-identical to runtime/comm/quant.py on
    both wires, both directions — non-finite markers, subnormal flush
    and the trailing ragged block included."""
    from deepspeed_tpu.runtime.comm.quant import (dequantize_blockwise_ref,
                                                  quantize_blockwise_ref)

    rng = np.random.RandomState(3)
    x = rng.randn(1000).astype(np.float32) * 10.0
    x[5], x[77], x[400] = np.inf, -np.inf, np.nan
    x[6] = 1e-40                       # subnormal -> flushed, scale 0 path
    x = jnp.asarray(x)
    block = 128

    pr, sr = quantize_blockwise_ref(x, block, wire)
    with kernel_config(interpret=True):
        pk, sk = registry.dispatch("quant_codec", x, block, wire,
                                   variant="quantize", impl="pallas")
    assert pk.dtype == pr.dtype and sk.dtype == sr.dtype
    assert np.array_equal(np.asarray(pk), np.asarray(pr))
    assert np.array_equal(np.asarray(sk), np.asarray(sr))

    yr = dequantize_blockwise_ref(pr, sr, wire, x.size)
    with kernel_config(interpret=True):
        yk = registry.dispatch("quant_codec", pr, sr, wire, x.size,
                               variant="dequantize", impl="pallas")
    assert yk.dtype == yr.dtype
    assert np.array_equal(np.asarray(yk), np.asarray(yr), equal_nan=True)


def test_public_quant_entry_routes_through_registry():
    """runtime/comm/quant.py's public blockwise entries ARE registry
    dispatches now — auto off-TPU lands on the oracle bit-for-bit and
    bumps the fallback counter."""
    from deepspeed_tpu.runtime.comm.quant import (quantize_blockwise,
                                                  quantize_blockwise_ref)

    x = jnp.asarray(np.random.RandomState(0).randn(300), jnp.float32)
    snap = COUNTERS.snapshot()
    p, s = quantize_blockwise(x, 128, "int8")
    pr, sr = quantize_blockwise_ref(x, 128, "int8")
    assert np.array_equal(np.asarray(p), np.asarray(pr))
    assert np.array_equal(np.asarray(s), np.asarray(sr))
    if not ON_TPU:
        d = COUNTERS.delta_since(snap)
        assert d.get("kernel.fallbacks", {}).get("calls", 0) >= 1


def _routing(N=16, E=4, C=5, k=2, D=128, seed=0):
    from deepspeed_tpu.moe.dispatch import topk_routing

    rng = np.random.RandomState(seed)
    e = np.exp(rng.randn(N, E))
    probs = jnp.asarray(e / e.sum(axis=1, keepdims=True), jnp.float32)
    eidx, gate, pos, keep, _ = topk_routing(probs, k, C)
    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    return x, eidx, gate, pos, keep, E, C


def test_moe_dispatch_parity_bit_exact():
    """The gather reformulation of the dispatch scatter is a BIT-exact
    permutation (kept destinations are unique) — dropped tokens zero,
    real routing from topk_routing."""
    from deepspeed_tpu.moe.dispatch import sorted_dispatch_ref

    x, eidx, gate, pos, keep, E, C = _routing()
    ref = sorted_dispatch_ref(x, eidx, pos, keep, E, C)
    with kernel_config(interpret=True):
        out = registry.dispatch("moe_dispatch", x, eidx, pos, keep, E, C,
                                variant="dispatch", impl="pallas")
    assert out.dtype == ref.dtype
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    # capacity actually dropped something, so the zero path is exercised
    assert not bool(np.all(np.asarray(keep)))


def test_moe_combine_parity_one_ulp():
    """Combine accumulates in the oracle's term order; the only
    divergence allowed is the accumulator's FMA fusion (~1 ulp)."""
    from deepspeed_tpu.moe.dispatch import sorted_combine_ref

    x, eidx, gate, pos, keep, E, C = _routing()
    expert_out = jnp.asarray(
        np.random.RandomState(1).randn(E, C, x.shape[-1]), jnp.float32)
    ref = sorted_combine_ref(expert_out, eidx, gate, pos, keep)
    with kernel_config(interpret=True):
        out = registry.dispatch("moe_dispatch", expert_out, eidx, gate,
                                pos, keep, variant="combine",
                                impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-6)


def _paged_inputs(kv_mode, R=2, T=1, H=2, Dh=128, bs=4, W=4, seed=0,
                  lengths=None, dead_to_trash=False, KV=None):
    """A pool `[rows, pool_width(KV or H, Dh)]`, tables [R, W] and query
    positions [R, T].  `lengths` [R]: what each slot holds once this
    call's rows are written (0: the slot is idle, its positions -1);
    None draws them.  `dead_to_trash`: table entries past a slot's
    length point at the trash block, as the allocator pads them.  `KV`:
    the rows hold that many heads, fewer than the H of the queries."""
    from deepspeed_tpu.runtime.comm.quant import quantize_rows
    from deepspeed_tpu.serving.kv_cache import pool_rows

    rng = np.random.RandomState(seed)
    nblocks = R * W + 1

    def pool():
        c = jnp.asarray(rng.randn(nblocks * bs, KV or H, Dh), jnp.float32)
        if kv_mode == "dense":
            return pool_rows(c)
        codes, scales = quantize_rows(c, kv_mode)
        return pool_rows(codes), scales

    ck, cv = pool(), pool()
    tables = rng.randint(1, nblocks, (R, W)).astype(np.int32)
    if lengths is None:
        lengths = rng.randint(T, W * bs + 1, (R,))
    lengths = np.asarray(lengths)
    if dead_to_trash:
        live = -(-lengths // bs)
        tables[np.arange(W)[None, :] >= live[:, None]] = 0
    # the queries are a slot's last T positions
    q_pos = np.where(lengths[:, None] > 0,
                     lengths[:, None] - T + np.arange(T)[None, :], -1)
    q = jnp.asarray(rng.randn(R, T, H, Dh), jnp.float32)
    return (q, ck, cv, jnp.asarray(tables),
            jnp.asarray(q_pos, jnp.int32), bs)


def _paged_both(kv_mode, scale=None, **kw):
    """(kernel, oracle, q_pos) of one call: `paged_attention`, or with
    `KV` heads a row `grouped_attention` — the walk at a grouped tile
    against the gather `_grouped_attend` ran before there was one."""
    from deepspeed_tpu.kernels.paged import paged_attention_reference
    from deepspeed_tpu.serving.layers import grouped_attention_reference

    q, ck, cv, tables, q_pos, bs = _paged_inputs(kv_mode, **kw)
    if kw.get("KV"):
        op = "grouped_attention"
        args = dict(kv_heads=kw["KV"], block_size=bs, scale=scale)
        ref = grouped_attention_reference(q, ck, cv, tables, q_pos, **args)
        assert ref.dtype == jnp.float32
    else:
        op, args = "paged_attention", dict(kv_mode=kv_mode, block_size=bs)
        ref = paged_attention_reference(q, ck, cv, tables, q_pos, **args)
    with kernel_config(interpret=True):
        out = registry.dispatch(op, q, ck, cv, tables, q_pos,
                                variant="default", impl="pallas", **args)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    shape = q.shape if kw.get("KV") else out.shape
    return (np.asarray(out, np.float32).reshape(shape),
            np.asarray(ref, np.float32).reshape(shape), q_pos)


# slots of length 1, one full block, one block plus one row, the full
# table, and an idle slot (bs 4, W 4)
_EDGE_LENGTHS = [1, 4, 5, 16, 0]


# the same on and beside the edges of blocks of 16 in a table of 20:
# several tiles of the walk, the last entry full
_EDGE_LENGTHS_16 = [1, 16, 17, 255, 257, 320, 0]
# Granite 4.0-H's rows (32 query heads on 8 K/V heads of 64, scores
# times 1/64) and Command A+'s (128 on 8 of 128)
_GRANITE = dict(H=32, KV=8, Dh=64, scale=1 / 64)
_COMMAND_A = dict(H=128, KV=8, Dh=128)


def _paged_case_id(v):
    if not isinstance(v, dict):
        return str(v)
    return "_".join(
        f"{k}{'x'.join(map(str, x)) if isinstance(x, list) else x}"
        for k, x in v.items()).replace("0.015625", "64th") or "H2_Dh128"


@pytest.mark.parametrize("kv_mode,T,shape", [
    ("dense", 1, {}), ("dense", 3, {}),
    ("int8", 1, {}), ("int8", 3, {}),
    ("int4", 1, {}), ("int4", 3, {}),
    # GPT-2 xl's heads (a row of 1,600 lanes in a pool of 1,664) and
    # chip_smoke's; decode and a verify step of draft_len + 1 = 4
    ("dense", 1, dict(H=25, Dh=64)), ("dense", 4, dict(H=25, Dh=64)),
    ("dense", 1, dict(H=16, Dh=128)), ("dense", 4, dict(H=16, Dh=128)),
    ("dense", 1, dict(H=25, Dh=64, R=5, lengths=_EDGE_LENGTHS)),
    ("dense", 1, dict(H=16, Dh=128, R=5, lengths=_EDGE_LENGTHS)),
    ("dense", 1, dict(H=25, Dh=64, R=5, lengths=_EDGE_LENGTHS,
                      dead_to_trash=True)),
    ("dense", 2, dict(H=25, Dh=64, R=5, lengths=[2, 4, 5, 16, 0],
                      dead_to_trash=True)),
    ("int8", 1, dict(H=25, Dh=64, R=5, lengths=_EDGE_LENGTHS,
                     dead_to_trash=True)),
    # a table wider than one tile of blocks: several steps of the walk
    ("dense", 1, dict(H=25, Dh=64, R=3, bs=16, W=40,
                      lengths=[640, 257, 30], dead_to_trash=True)),
    # grouped rows, a tile of the row's K/V heads serving G query heads
    # a key: idle slots, one row, a block's edges, the full table
    ("dense", 1, dict(_GRANITE, R=5, lengths=_EDGE_LENGTHS)),
    ("dense", 1, dict(_GRANITE, R=5, lengths=_EDGE_LENGTHS,
                      dead_to_trash=True)),
    ("dense", 1, dict(_GRANITE, R=7, bs=16, W=20,
                      lengths=_EDGE_LENGTHS_16, dead_to_trash=True)),
    ("dense", 1, dict(_COMMAND_A, R=5, lengths=_EDGE_LENGTHS)),
    ("dense", 1, dict(_COMMAND_A, R=7, bs=16, W=20,
                      lengths=_EDGE_LENGTHS_16, dead_to_trash=True)),
    # a verify step's queries, and rows narrower than a lane tile (the
    # toy models of tests/test_granite_hybrid.py, test_cohere2_moe.py)
    ("dense", 3, dict(_GRANITE, R=5, lengths=[3, 4, 5, 16, 0],
                      dead_to_trash=True)),
    ("dense", 1, dict(H=8, KV=2, Dh=16, R=5, lengths=_EDGE_LENGTHS)),
    ("dense", 2, dict(H=4, KV=2, Dh=16, R=5, lengths=[2, 4, 5, 16, 0])),
], ids=_paged_case_id)
def test_paged_attention_parity(kv_mode, T, shape):
    """The table walk (quantized dequant done on the tile) vs the
    gather/einsum/softmax expression — decode (T=1) and short verify
    windows.  An idle slot (positions -1) reads nothing in the kernel
    and its output is discarded by the engine: not compared."""
    out, ref, q_pos = _paged_both(kv_mode, T=T, **shape)
    live = np.asarray(q_pos)[:, -1] >= 0
    np.testing.assert_allclose(out[live], ref[live], atol=2e-6)
    assert not out[~live].any()


@pytest.mark.parametrize("shape", [dict(H=25, Dh=64), _GRANITE,
                                   _COMMAND_A], ids=_paged_case_id)
def test_paged_attention_slot_ignores_the_other_slots(shape):
    """Batching invariance of the kernel: a slot's output is the same
    bits whatever the other slots hold — long, short or idle."""
    kw = dict(shape, R=3, bs=4, W=8, dead_to_trash=True)
    a, _, _ = _paged_both("dense", lengths=[13, 32, 7], **kw)
    b, _, _ = _paged_both("dense", lengths=[13, 1, 0], **kw)
    assert np.array_equal(a[0], b[0])


def test_grouped_attention_kernel_refuses_a_ring_under_no_window():
    """Handed a ring's newest position and no window — rows that lie by
    position with nothing to say which of them a query sees — the kernel
    says so and computes nothing, for a chunk and for a decode step."""
    for T in (16, 1):
        q, ck, cv, tables, q_pos, bs = _paged_inputs(
            "dense", T=T, H=4, KV=2, Dh=128, bs=8, W=8, lengths=[40])
        with kernel_config(interpret=True), \
                pytest.raises(ValueError, match="a ring .* under no window"):
            registry.dispatch("grouped_attention", q, ck, cv, tables, q_pos,
                              impl="pallas", kv_heads=2, block_size=bs,
                              newest=q_pos[:, -1])


def _sliding_both(p, T=1, H=8, KV=2, Dh=16, bs=8, M=6, window=32, ring=True,
                  scale=0.3, seed=0):
    """(kernel, oracle, live) of one decode or verify call of a sliding
    layer: slots whose newest positions are `p` (negative: idle), each
    with its own run of `M` blocks of `bs` rows — a ring, the rows by
    position modulo `M * bs`, or (`ring` false) the table, which no
    position laps — and its last `T` positions as queries."""
    from deepspeed_tpu.serving.kv_cache import pool_rows
    from deepspeed_tpu.serving.layers import grouped_attention_reference

    rng = np.random.RandomState(seed)
    p = np.asarray(p)
    R = len(p)
    ck, cv = (pool_rows(jnp.asarray(
        rng.randn((R * M + 1) * bs, KV, Dh), jnp.float32)) for _ in range(2))
    tables = jnp.asarray(
        1 + rng.permutation(R * M).reshape(R, M), jnp.int32)
    q_pos = p[:, None] - T + 1 + np.arange(T)[None, :]
    q_pos = jnp.asarray(np.where(q_pos < 0, -1, q_pos), jnp.int32)
    q = jnp.asarray(rng.randn(R, T, H, Dh), jnp.float32)
    args = dict(kv_heads=KV, block_size=bs, scale=scale, window=window,
                newest=jnp.asarray(np.maximum(p, 0), jnp.int32)
                if ring else None)
    ref = grouped_attention_reference(q, ck, cv, tables, q_pos, **args)
    with kernel_config(interpret=True):
        out = registry.dispatch("grouped_attention", q, ck, cv, tables, q_pos,
                                impl="pallas", **args)
    assert out.shape == ref.shape and out.dtype == ref.dtype == jnp.float32
    return np.asarray(out), np.asarray(ref), p >= 0


# a ring of L = 48 rows (6 blocks of 8) under a window of 32
_L = 48


@pytest.mark.parametrize("case", [
    # a slot shorter than the window, on and off a block's edge
    dict(p=[0, 5, 7, 8, 30]),
    # the newest position at window - 1, window, L - 1, L, L + 1
    dict(p=[31, 32, _L - 1, _L, _L + 1]),
    # several laps; the lower bound on a block's edge (p - 31 = 8 k) and
    # off it; the run wrapping the ring's last block into its first
    dict(p=[3 * _L + 7, 5 * _L, 39, 40, _L + 7, 2 * _L + 15]),
    # an idle slot beside live ones
    dict(p=[-1, _L + 3, -1, 12]),
    # a verify step's four queries: each its own lower bound, the oldest
    # query's the walk's; a slot that holds the step alone
    dict(p=[5, 31, 32, 34, _L - 1, _L, _L + 2, 3 * _L + 7, -1, 3], T=4),
    dict(p=[2 * _L + 1, 40, -1], T=4, M=7),
    # the window on the table (`ring_blocks` 0): the same rule, no wrap
    dict(p=[5, 31, 32, 40, 63, 0], M=8, ring=False),
    dict(p=[34, 63, 9, -1], M=8, ring=False, T=4),
    # the window a whole number of blocks short of the run by one block
    # (the least the registry lets through at q_len 1: 32 + 8 = 40)
    dict(p=[39, 40, 41, 200, 7], M=5),
    # Command A+'s heads and Granite's: G = 16 and 4, lanes of whole
    # tiles; blocks of 16 over several tiles of the walk
    dict(p=[5, 300, 4 * 320 + 17, -1], H=128, KV=8, Dh=128, bs=16, M=20,
         window=288, scale=None),
    dict(p=[287, 288, 319, 320, 321], H=32, KV=8, Dh=64, bs=16, M=20,
         window=288, scale=1 / 64),
    dict(p=[700, 3, -1], H=16, KV=1, Dh=128, bs=16, M=4, window=40),
], ids=_paged_case_id)
def test_sliding_walk_parity(case):
    """A sliding layer's decode or verify call: the walk of the window's
    live blocks modulo the run — its mask linear in the walked row, each
    query's own bounds — against the gather of the whole run under
    `_visible` over `newest - (newest - j) % L`.  An idle slot reads
    nothing and leaves zeros; the engine discards them."""
    out, ref, live = _sliding_both(**case)
    np.testing.assert_allclose(out[live], ref[live], atol=5e-6)
    assert not out[~live].any()


def test_sliding_walk_reads_only_the_windows_blocks():
    """The walk copies the blocks the window lies in and no other: with
    NaN in every other block of a wrapped slot's ring and of a short
    slot's, the output is finite and the oracle's (whose gather weighs
    those rows by exactly 0 only where they are finite: compared on a
    clean copy)."""
    from deepspeed_tpu.kernels.paged import grouped_attention_pallas
    from deepspeed_tpu.serving.kv_cache import pool_rows
    from deepspeed_tpu.serving.layers import grouped_attention_reference

    rng = np.random.RandomState(0)
    bs, M, KV, Dh, H, window = 8, 8, 2, 16, 8, 24
    p = np.array([2 * M * bs + 3, 13])           # wrapped; short
    tables = 1 + rng.permutation(2 * M).reshape(2, M).astype(np.int32)
    clean = [rng.randn((2 * M + 1) * bs, KV, Dh).astype(np.float32)
             for _ in range(2)]
    dirty = [c.copy() for c in clean]
    for slot, newest in enumerate(p):
        lo = max(0, newest - window + 1)
        held = {b % M for b in range(lo // bs, newest // bs + 1)}
        for entry in set(range(M)) - held:
            for c in dirty:
                c[tables[slot, entry] * bs:][:bs] = np.nan
    q = jnp.asarray(rng.randn(2, 1, H, Dh), jnp.float32)
    args = dict(kv_heads=KV, block_size=bs, window=window,
                newest=jnp.asarray(p, jnp.int32))
    pools = lambda cs: [pool_rows(jnp.asarray(c)) for c in cs]
    q_pos = jnp.asarray(p[:, None], jnp.int32)
    ref = grouped_attention_reference(q, *pools(clean), jnp.asarray(tables),
                                      q_pos, **args)
    with kernel_config(interpret=True):
        out = grouped_attention_pallas(q, *pools(dirty), jnp.asarray(tables),
                                       q_pos, **args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def _sliding_chunk_both(pos0, T=16, H=4, KV=2, Dh=128, bs=8, M=6, window=32,
                        ring=True, dtype=jnp.float32, nan_elsewhere=False,
                        seed=0):
    """(kernel, oracle) [T, H * Dh] of one request's prefill chunk of a
    sliding layer: T queries at positions pos0 upward over its run of `M`
    blocks of `bs` rows — a ring, the rows by position modulo `M * bs`
    with the chunk's last position the newest written, or (`ring` false)
    the table.  `nan_elsewhere`: every block no query's window reaches
    holds NaN in the kernel's pool (the oracle reads a clean copy: its
    gather weighs those rows by exactly 0 only where they are finite)."""
    from deepspeed_tpu.serving.kv_cache import pool_rows
    from deepspeed_tpu.serving.layers import grouped_attention_reference

    rng = np.random.RandomState(seed)
    table = 1 + rng.permutation(M).astype(np.int32)
    clean = [rng.randn(M + 1, bs, KV, Dh).astype(np.float32)
             for _ in range(2)]
    dirty = [c.copy() for c in clean]
    if nan_elsewhere:
        held = {b % M for b in range(max(0, pos0 - window + 1) // bs,
                                     (pos0 + T - 1) // bs + 1)}
        for c in dirty:
            c[np.setdiff1d(np.arange(M + 1), table[sorted(held)])] = np.nan
    pools = lambda cs: [pool_rows(jnp.asarray(c.reshape(-1, KV, Dh), dtype))
                        for c in cs]
    q_pos = jnp.asarray(pos0 + np.arange(T), jnp.int32)[None]
    q = jnp.asarray(rng.randn(1, T, H, Dh), dtype)
    args = dict(kv_heads=KV, block_size=bs, scale=None, window=window,
                newest=q_pos[:, -1] if ring else None)
    tables = jnp.asarray(table)[None]
    ref = grouped_attention_reference(q, *pools(clean), tables, q_pos, **args)
    with kernel_config(interpret=True):
        out = registry.dispatch("grouped_attention", q, *pools(dirty), tables,
                                q_pos, impl="pallas", **args)
    assert out.shape == ref.shape == (1, T, H * Dh)
    assert out.dtype == ref.dtype == jnp.float32
    return np.asarray(out)[0], np.asarray(ref)[0]


@pytest.mark.parametrize("case", [
    # a ring of 48 rows (6 blocks of 8) under a window of 32, the
    # engine's `window + chunk`: a first chunk, and one before the
    # window fills
    dict(pos0=0),
    dict(pos0=16, nan_elsewhere=True),
    # the chunk that fills the ring, the first to wrap it, one several
    # laps in
    dict(pos0=32),
    dict(pos0=48, nan_elsewhere=True),
    dict(pos0=5 * _L + 16, nan_elsewhere=True),
    # chunks off a block's edge: the walk starts inside a block, and a
    # run of exactly `window + T - 1` rows (33 + 16 - 1 = 48) whose
    # oldest block is its newest too, copied at both ends of the walk
    dict(pos0=37),
    dict(pos0=3 * _L + 5),
    dict(pos0=41, window=33),
    dict(pos0=2 * _L + 9, window=33),
    # a tile of 64 query positions on both sides of the ring's wrap
    # (1,056 .. 1,119 over a ring of 1,088 rows), its walk three tiles
    # of 512 rows: masked / whole / masked; and two such tiles, a
    # program each, three laps in
    dict(pos0=1088 - 32, T=64, H=2, KV=1, M=136, window=1024),
    dict(pos0=3 * 1152 - 40, T=128, H=2, KV=1, M=144, window=1000,
         nan_elsewhere=True),
    # the window on the table (`ring_blocks` 0): the same rule, no wrap
    dict(pos0=5, ring=False, M=8),
    dict(pos0=40, ring=False, M=8, nan_elsewhere=True),
    # a last chunk's padded tail past the table (64 rows): 5 valid rows
    dict(pos0=51, ring=False, M=8, valid=5),
    # Command A+'s 16 query heads a K/V head; a bf16 pool
    dict(pos0=2 * _L + 16, H=32, KV=2, nan_elsewhere=True),
    dict(pos0=16, dtype=jnp.bfloat16, atol=2e-2),
    dict(pos0=3 * _L + 5, dtype=jnp.bfloat16, atol=2e-2),
], ids=_paged_case_id)
def test_sliding_chunk_walk_parity(case):
    """A sliding layer's prefill chunk: each tile of query positions
    walks the blocks from its oldest lower bound to its last position,
    modulo the run, and no other — its mask linear in the walked row,
    two bounds a query — against the gather of the whole run under
    `_visible` over `newest - (newest - j) % L`."""
    case = dict(case)
    valid, atol = case.pop("valid", None), case.pop("atol", 3e-6)
    out, ref = _sliding_chunk_both(**case)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[:valid], ref[:valid], atol=atol)


def test_grouped_oracle_is_the_expression_the_layer_ran():
    """`grouped_attention` through the registry off the chip IS
    `attend_grouped` over the gathered table under `_visible`, bit for
    bit — under a window and over a ring too."""
    from deepspeed_tpu.models.cohere2_moe import attend_grouped
    from deepspeed_tpu.serving.layers import _visible

    q, ck, cv, tables, q_pos, bs = _paged_inputs(
        "dense", H=8, KV=2, Dh=16, R=3, bs=4, W=4, lengths=[16, 7, 0])
    L, lanes = 16, ck.shape[1]
    held = lambda c: c.reshape(-1, bs, lanes)[tables].reshape(
        3, -1, lanes)[..., :32].reshape(3, -1, 2, 16)
    at = jnp.arange(L)[None, :]
    for window, newest in ((0, None), (5, None), (5, q_pos[:, 0] + 20)):
        rows = at if newest is None else \
            newest[:, None] - (newest[:, None] - at) % L
        want = attend_grouped(q, held(ck), held(cv),
                              _visible(rows, q_pos, window), scale=0.3)
        got = registry.dispatch(
            "grouped_attention", q, ck, cv, tables, q_pos,
            info={"ring": newest is not None, "window": window},
            kv_heads=2, block_size=bs, scale=0.3, window=window,
            newest=newest)
        assert np.array_equal(np.asarray(got), np.asarray(want))


_GRANITE_INFO = dict(block_size=16, table_width=128, q_len=1, num_heads=32,
                     kv_heads=8, head_dim=64, kv_mode="dense",
                     kv_itemsize=2, window=0, ring=False)


@pytest.mark.parametrize("change,why", [
    ({}, None),
    (dict(q_len=4), None),
    # Command A+'s full layer: 128 score rows of 1,024 lanes
    (dict(num_heads=128, head_dim=128, table_width=1024), None),
    # a prefill chunk of Granite's heads: half a lane tile each
    (dict(q_len=512), "8 K/V heads of 64 values.*whole 128-lane tiles"),
    # a sliding layer's decode call: the window on the table, and in a
    # ring long enough that the walk's linear mask holds (Command A+'s
    # 288 blocks); a shorter ring keeps the gather
    (dict(window=4096), None),
    (dict(window=4096, ring=True, table_width=288), None),
    (dict(window=4096, ring=True, table_width=257), None),
    (dict(window=4096, ring=True, table_width=257, q_len=4),
     "a ring of 4112 rows under a window of 4096.*a run of 4115 rows"),
    (dict(window=4096, ring=True, table_width=256),
     "a ring of 4096 rows under a window of 4096.*a run of 4112 rows"),
    (dict(block_size=8), "a block of 8 rows is not whole tiles"),
    (dict(kv_mode="int8"), "int8 rows"),
    (dict(q_len=8, num_heads=128, head_dim=128),
     "8 x 128 score rows of 1024 lanes"),
], ids=lambda v: _paged_case_id(v) if isinstance(v, dict) else "")
def test_grouped_attention_shape_rule(change, why, native):
    """What the call site can see decides (serving/layers.py::
    grouped_info): a decode call takes the walk on the chip at the
    grouped tile — a full layer's one causal run, a sliding layer's
    window modulo its run; prefill, a ring too short for the walk's mask
    and every shape the walk cannot copy take the gather and say what is
    missing when the kernel is forced."""
    info = dict(_GRANITE_INFO, **change)
    if why is None:
        assert resolve_impl("grouped_attention", info=info) == "pallas"
        return
    assert resolve_impl("grouped_attention", info=info) == "jnp"
    with pytest.raises(RuntimeError, match=why):
        resolve_impl("grouped_attention", impl="pallas", info=info)


def test_grouped_attention_is_the_oracle_off_the_chip():
    assert resolve_impl("grouped_attention", info=_GRANITE_INFO) == "jnp"


# -- a prefill chunk over grouped rows: the one request's live blocks ---------


def _prefill_both(pos0, T=16, H=4, KV=2, Dh=128, bs=8, W=8, seed=0,
                  nan_behind=False):
    """(kernel, oracle) [T, H * Dh] of one request's chunk of T queries
    at positions pos0 upward through a table of W entries.
    `nan_behind`: every block the run does not reach — the table's
    entries behind it and the blocks no entry names — holds NaN, which
    the oracle's gather, reading zeros there, weighs by exactly 0."""
    from deepspeed_tpu.serving.kv_cache import pool_rows
    from deepspeed_tpu.serving.layers import grouped_attention_reference

    rng = np.random.RandomState(seed)
    nblocks = W + 4
    table = rng.permutation(np.arange(1, nblocks))[:W].astype(np.int32)
    q_pos = pos0 + np.arange(T)
    live = -(-min(int(q_pos[-1]) + 1, W * bs) // bs)
    pools, clean = [], []
    for _ in range(2):
        c = rng.randn(nblocks, bs, KV, Dh).astype(np.float32)
        dead = np.setdiff1d(np.arange(nblocks), table[:live])
        clean.append(np.where(np.isin(np.arange(nblocks), dead)[
            :, None, None, None], 0.0, c))
        if nan_behind:
            c[dead] = np.nan
        pools.append(c)
    as_pool = lambda c: pool_rows(jnp.asarray(c).reshape(-1, KV, Dh))
    q = jnp.asarray(rng.randn(1, T, H, Dh), jnp.float32)
    tables, q_pos = jnp.asarray(table)[None], jnp.asarray(q_pos,
                                                          jnp.int32)[None]
    args = dict(kv_heads=KV, block_size=bs, scale=None)
    with kernel_config(interpret=True):
        out = registry.dispatch(
            "grouped_attention", q, *map(as_pool, pools), tables, q_pos,
            impl="pallas", **args)
    ref = grouped_attention_reference(
        q, *map(as_pool, clean if nan_behind else pools), tables, q_pos,
        **args)
    assert out.shape == ref.shape == (1, T, H * Dh)
    assert out.dtype == ref.dtype == jnp.float32
    return np.asarray(out)[0], np.asarray(ref)[0]


@pytest.mark.parametrize("pos0,valid,shape", [
    (0, 16, {}),                       # a first chunk, position 0 upward
    (16, 16, {}),
    (21, 16, {}),                      # the run ends inside a block
    # a last chunk's padded tail: positions past `n_valid`, the last
    # three past the table (64 rows): the valid rows agree
    (51, 5, {}),
    (56, 8, dict(nan_behind=True)),
    # the run's end and everything behind it is never fetched
    (21, 16, dict(nan_behind=True)),
    # several tiles of the walk, whole ones unmasked, two tiles of query
    # positions a program each; Command A+'s 16 query heads a K/V head
    (600, 32, dict(T=32, W=80, nan_behind=True)),
    (1040, 16, dict(T=64, W=160, H=2, KV=1, nan_behind=True)),
    (40, 16, dict(H=32, KV=2, W=16, nan_behind=True)),
], ids=_paged_case_id)
def test_grouped_prefill_walk_parity(pos0, valid, shape):
    """The prefill walk against `grouped_attention_reference`: a chunk's
    queries read the request's blocks from the table's first entry to
    the one the chunk's last position needs, and nothing behind it."""
    out, ref = _prefill_both(pos0, **shape)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[:valid], ref[:valid], atol=2e-6)


def test_grouped_prefill_walk_refuses_what_it_cannot_tile():
    """Forced onto several sequences, or heads of half a lane tile, the
    kernel says so and computes nothing."""
    for kw in (dict(R=2, T=16, H=4, KV=2, Dh=128), dict(R=1, T=16, **{
            k: v for k, v in _GRANITE.items() if k != "scale"})):
        q, ck, cv, tables, q_pos, bs = _paged_inputs("dense", **kw)
        with kernel_config(interpret=True), \
                pytest.raises(ValueError, match="a prefill chunk's walk"):
            registry.dispatch("grouped_attention", q, ck, cv, tables, q_pos,
                              impl="pallas", kv_heads=kw["KV"], block_size=bs)


# Command A+'s full layer's prefill call in its cell: one request's chunk
# of 512 queries of 128 heads on 8 K/V heads of 128, a table of 1,024
_COMMAND_A_CHUNK = dict(_GRANITE_INFO, q_len=512, num_heads=128,
                        head_dim=128, table_width=1024, batch=1)


@pytest.mark.parametrize("change,why", [
    ({}, None),
    (dict(q_len=1024), None),
    (dict(kv_itemsize=4, block_size=8), None),
    # a sliding layer's chunk: the window on the table, and in a ring
    # that holds `window + q_len - 1` rows (Command A+'s 288 blocks, the
    # engine's window + chunk); a shorter ring keeps the gather
    (dict(window=4096), None),
    (dict(window=4096, ring=True, table_width=288), None),
    (dict(window=4096, ring=True, table_width=287),
     "a ring of 4592 rows under a window of 4096.*no row a query of the "
     "call's 512 sees.*a run of 4607 rows"),
    (dict(head_dim=64), "8 K/V heads of 64 values.*whole 128-lane tiles"),
    (dict(batch=2), "2 sequences of 512 queries.*one request's table"),
    (dict(kv_mode="int8"), "int8 rows"),
    (dict(block_size=8), "a block of 8 rows is not whole tiles"),
    (dict(q_len=500), "no tile of whole sublanes.*divides the 500"),
    (dict(num_heads=1024, kv_heads=64), "fits the kernel's VMEM"),
], ids=lambda v: _paged_case_id(v) if isinstance(v, dict) else "")
def test_grouped_prefill_shape_rule(change, why, native):
    """A prefill call takes the walk on the chip where what the call
    site sees allows it — one request, dense rows in whole tiles, heads
    of whole lane tiles, tiles that fit VMEM, and under a window a ring
    no query's rows were lapped in — and everything else keeps the
    gather, with the reason."""
    info = dict(_COMMAND_A_CHUNK, **change)
    if why is None:
        assert resolve_impl("grouped_attention", info=info) == "pallas"
        return
    assert resolve_impl("grouped_attention", info=info) == "jnp"
    with pytest.raises(RuntimeError, match=why):
        resolve_impl("grouped_attention", impl="pallas", info=info)


@pytest.mark.parametrize("op", ["paged_attention", "latent_attention",
                                "eva_attention"])
def test_other_walks_keep_prefill_on_the_oracle(op, native):
    """Only grouped rows have a prefill walk: the other families' chunks
    are ROADMAP S11's later cases."""
    info = dict(_COMMAND_A_CHUNK, kv_heads=1, window=2048, chunk=16)
    assert resolve_impl(op, info=info) == "jnp"
    with pytest.raises(RuntimeError, match="q_len 512 is a prefill chunk"):
        resolve_impl(op, impl="pallas", info=info)


# -- latent rows: the walk at one K/V head a row, one operand -----------------

# DeepSeek-V2-Lite's row in the chatgen cell: 16 heads, the latent c of
# 512 beside a rotated key of 64, 576 values in a pool row of 640 lanes
_LATENT = dict(H=16, rank=512, rope=64)


def _latent_both(T=1, H=16, rank=512, rope=64, scale=0.1147, **kw):
    """(kernel, oracle, q_pos) of one `latent_attention` call: queries as
    rows-shaped [R, T, H, rank + rope] over a pool that is key and value
    at once -> [R, H, T, rank] float32."""
    from deepspeed_tpu.serving.layers import latent_attention_reference

    q, pool, _, tables, q_pos, bs = _paged_inputs(
        "dense", T=T, H=H, KV=1, Dh=rank + rope, **kw)
    assert pool.shape[1] % 128 == 0 and pool.shape[1] >= rank + rope
    args = dict(block_size=bs, rank=rank, scale=scale)
    ref = latent_attention_reference(q, pool, tables, q_pos, **args)
    with kernel_config(interpret=True):
        out = registry.dispatch("latent_attention", q, pool, tables, q_pos,
                                impl="pallas", **args)
    assert out.shape == ref.shape == (q.shape[0], H, T, rank)
    assert out.dtype == ref.dtype == jnp.float32
    return np.asarray(out), np.asarray(ref), q_pos


@pytest.mark.parametrize("T,shape", [
    # the cell's tile, blocks of 16 in a table of 20 (two tiles of the
    # walk): scattered slots on and beside the edges of blocks
    (1, dict(_LATENT, R=7, bs=16, W=20, lengths=_EDGE_LENGTHS_16,
             dead_to_trash=True)),
    (1, dict(_LATENT, R=7, bs=16, W=20, lengths=_EDGE_LENGTHS_16)),
    (2, dict(_LATENT, R=7, bs=16, W=20,
             lengths=[2, 16, 17, 256, 257, 320, 0], dead_to_trash=True)),
    # live lists of none, one and all of the slots
    (1, dict(_LATENT, R=3, bs=16, W=20, lengths=[0, 0, 0])),
    (1, dict(_LATENT, R=3, bs=16, W=20, lengths=[0, 37, 0],
             dead_to_trash=True)),
    (1, dict(_LATENT, R=3, bs=16, W=20, lengths=[320, 48, 101],
             dead_to_trash=True)),
    (2, dict(_LATENT, R=3, bs=16, W=20, lengths=[0, 0, 0])),
    (2, dict(_LATENT, R=3, bs=16, W=20, lengths=[0, 33, 0])),
    (2, dict(_LATENT, R=3, bs=16, W=20, lengths=[320, 48, 101])),
    # rows narrower than a lane tile (tests/test_deepseek_v2.py's toy:
    # 4 heads, rank 32 + rope 16)
    (1, dict(H=4, rank=32, rope=16, R=5, bs=8, W=4,
             lengths=[1, 8, 9, 32, 0])),
    (2, dict(H=4, rank=32, rope=16, R=5, bs=8, W=4,
             lengths=[2, 8, 9, 32, 0], dead_to_trash=True)),
], ids=_paged_case_id)
def test_latent_attention_parity(T, shape):
    """The walk over latent rows — one array scored and summed over,
    the value its first `rank` lanes — vs the gather of every table
    entry under the causal mask.  An idle slot reads nothing and its
    output, which the engine discards, is zeros.  (A score is a float32
    sum of 576 products of unit normals, so its rounding is some 4e-6
    and moves a probability by as much: against float64 the oracle
    stands 2e-6 off and the kernel 6e-6.)"""
    out, ref, q_pos = _latent_both(T=T, **shape)
    live = np.asarray(q_pos)[:, -1] >= 0
    np.testing.assert_allclose(out[live], ref[live], atol=2e-5)
    assert not out[~live].any()


def test_latent_attention_slot_ignores_the_other_slots():
    kw = dict(_LATENT, R=3, bs=16, W=20, dead_to_trash=True)
    a, _, _ = _latent_both(lengths=[37, 320, 7], **kw)
    b, _, _ = _latent_both(lengths=[37, 1, 0], **kw)
    assert np.array_equal(a[0], b[0])


def test_latent_walk_copies_a_block_once():
    """Key and value are one array: the call hands the kernel one
    operand, and its program starts half the copies a block that the
    same walk over a K and a V array does."""
    from deepspeed_tpu.kernels import paged

    q, pool, cv, tables, q_pos, bs = _paged_inputs(
        "dense", H=16, KV=1, Dh=576, R=2, bs=16, W=20)

    def walk(fn):
        text = str(jax.make_jaxpr(fn)())
        call = next(ln for ln in text.splitlines() if "pallas_call" in ln)
        return text.count("dma_start"), text.count("dma_wait"), call

    with kernel_config(interpret=True):
        one = walk(lambda: paged.latent_attention_pallas(
            q, pool, tables, q_pos, block_size=bs, rank=512, scale=0.1))
        two = walk(lambda: paged.grouped_attention_pallas(
            q, pool, cv, tables, q_pos, kv_heads=1, block_size=bs,
            scale=0.1))
    assert one[0] > 0 and one[1] > 0
    assert (2 * one[0], 2 * one[1]) == two[:2]


def test_latent_oracle_is_the_expression_the_layer_ran():
    """`latent_attention` through the registry off the chip IS the gather
    of every table entry and `attend_absorbed`'s scores, softmax and
    weighted sum under `q_pos >= arange(L)`, bit for bit — alone, and
    inside the two W_kv_b products."""
    from deepspeed_tpu.models import DeepSeekV2Config
    from deepspeed_tpu.models import deepseek_v2 as dsv2

    cfg = DeepSeekV2Config(
        vocab_size=128, max_seq_len=128, num_layers=1, num_heads=4,
        d_model=64, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, d_ff=96, first_k_dense=1,
        num_experts=8, top_k=3, num_shared_experts=1, d_expert=48,
        yarn=dsv2.Yarn(40.0, 64, 32.0, 1.0, 0.707, 0.707))
    q, pool, _, tables, q_pos, bs = _paged_inputs(
        "dense", H=4, KV=1, Dh=48, R=3, bs=4, W=4, lengths=[16, 7, 0])
    rng = np.random.RandomState(1)
    kv_b = jnp.asarray(rng.randn(32, 4 * 32) * 0.2, jnp.float32)
    q_nope = jnp.asarray(rng.randn(3, 1, 4, 16), jnp.float32)
    q_rope = q[..., 32:]
    lanes = pool.shape[1]
    held = pool.reshape(-1, bs, lanes)[tables].reshape(
        3, -1, lanes)[..., :48]
    mask = q_pos[:, :, None] >= jnp.arange(16)[None, None, :]
    scale = dsv2.softmax_scale(cfg.head_dim, cfg.yarn)
    through = lambda q_row: registry.dispatch(
        "latent_attention", q_row, pool, tables, q_pos,
        info={"q_len": 1, "kv_heads": 1, "head_dim": 48}, block_size=bs,
        rank=32, scale=scale)
    assert np.array_equal(
        np.asarray(through(q)),
        np.asarray(dsv2.attend_rows(q, held, mask, 32, scale)))
    want = dsv2.attend_absorbed(cfg, kv_b, q_nope, q_rope, held, mask)
    got = dsv2.absorbed_attention(cfg, kv_b, q_nope, q_rope, jnp.float32,
                                  through)
    assert want.shape == (3, 1, 4 * 16)
    assert np.array_equal(np.asarray(got), np.asarray(want))


_LATENT_INFO = dict(block_size=16, table_width=256, q_len=1, num_heads=16,
                    kv_heads=1, head_dim=576, kv_mode="dense",
                    kv_itemsize=2)


@pytest.mark.parametrize("change,why", [
    ({}, None),
    (dict(q_len=2), None),
    # DeepSeek-V2's 128 heads over the same row
    (dict(num_heads=128), None),
    (dict(q_len=512), "q_len 512 is a prefill chunk"),
    (dict(kv_mode="int8"), "int8 rows"),
    (dict(block_size=8), "a block of 8 rows is not whole tiles"),
    (dict(block_size=24), "a block of 24 rows is not whole tiles"),
    (dict(q_len=8, num_heads=128, head_dim=1088),
     "8 x 128 score rows of 1152 lanes"),
], ids=lambda v: _paged_case_id(v) if isinstance(v, dict) else "")
def test_latent_attention_shape_rule(change, why, native):
    """What the call site can see decides (serving/layers.py::
    latent_info): a decode or verify call over dense latent rows takes
    the walk on the chip; a prefill chunk, quantized rows and every
    shape the walk cannot copy take the gather and say what is missing
    when the kernel is forced."""
    info = dict(_LATENT_INFO, **change)
    if why is None:
        assert resolve_impl("latent_attention", info=info) == "pallas"
        return
    assert resolve_impl("latent_attention", info=info) == "jnp"
    with pytest.raises(RuntimeError, match=why):
        resolve_impl("latent_attention", impl="pallas", info=info)


def test_latent_attention_is_the_oracle_off_the_chip():
    assert resolve_impl("latent_attention", info=_LATENT_INFO) == "jnp"


def test_latent_info_is_what_the_call_site_sees():
    """`latent_info` of the chatgen cell's decode call: the shapes, no
    model's name, no option."""
    from deepspeed_tpu.models import DeepSeekV2Config
    from deepspeed_tpu.serving import ServeSchedule
    from deepspeed_tpu.serving.layers import latent_info

    cfg = DeepSeekV2Config(num_layers=9, param_dtype=jnp.bfloat16)
    sched = ServeSchedule(max_batch=32, prefill_chunk=512, block_size=16,
                          num_blocks=8193, table_width=256)
    assert latent_info(cfg, sched, 1, jnp.bfloat16,
                       cfg.latent_width) == _LATENT_INFO


def _eva_inputs(positions, T=1, H=2, Dh=64, bs=4, window=32, chunk=4,
                summary_blocks=8, dtype=jnp.float32, seed=0):
    """A pool `[rows, pool_width(H, Dh)]`, tables `[window blocks |
    summary blocks]` and query positions [R, T] for slots whose last
    query stands at `positions` (negative: the slot is not running).
    Table entries outside the two live runs of a slot point at the
    trash block, as the allocator leaves them; every block, the trash
    block too, holds noise."""
    from deepspeed_tpu.serving.kv_cache import pool_width

    rng = np.random.RandomState(seed)
    R, wb = len(positions), window // bs
    W = wb + summary_blocks
    nblocks = R * W + 1
    pool = lambda: jnp.asarray(
        rng.randn(nblocks * bs, pool_width(H, Dh)), dtype)
    ck, cv = pool(), pool()
    tables = np.zeros((R, W), np.int32)
    ids = rng.permutation(np.arange(1, nblocks))
    for r, p in enumerate(positions):
        if p < 0:
            continue
        n_win = p % window // bs + 1
        n_sum = -(-(p // window * (window // chunk)) // bs)
        mine = ids[r * W:(r + 1) * W]
        tables[r, :n_win] = mine[:n_win]
        tables[r, wb:wb + n_sum] = mine[wb:wb + n_sum]
    last = np.asarray(positions)[:, None]
    q_pos = np.where(last >= 0, last - T + 1 + np.arange(T)[None, :], -1)
    q = jnp.asarray(rng.randn(R, T, H, Dh), dtype)
    return (q, ck, cv, jnp.asarray(tables), jnp.asarray(q_pos, jnp.int32),
            dict(window=window, chunk=chunk, block_size=bs))


_EVA_CELL = dict(bs=16, window=2048, chunk=16, summary_blocks=64)


def _eva_case_id(v):
    if isinstance(v, list):
        return "pos" + "x".join(map(str, v))
    return _paged_case_id({k: getattr(x, "__name__", x)
                           for k, x in v.items()}) if v else ""


@pytest.mark.parametrize("positions,shape", [
    # inside the first window: no summary run
    ([5], {}), ([0], {}), ([31], {}),
    # the first position of a new window: one row of one window block,
    # and the summaries of the window just closed
    ([32], {}), ([64], {}),
    # 6 summary rows a window: the last summary block is part visible
    ([24 + 7], dict(window=24)), ([3 * 24], dict(window=24)),
    # EvaByte's own sizes: the last position a table of 128 + 64 holds,
    # several tiles of the walk, a tile across the two runs
    ([16383], _EVA_CELL), ([9000, 2048, 100], _EVA_CELL),
    # a batch: mid-window, an idle slot, a new window, a first window
    ([45, -1, 96, 3], {}),
    ([45, -1, 96, 3], dict(H=4, Dh=32)),        # a row of 128 lanes
    ([45, -1, 96, 3], dict(H=3, Dh=32)),        # ... padded to 128
    ([45, -1, 96, 3], dict(dtype=jnp.bfloat16)),
    # several queries a slot, one pair across a window's close
    ([45, -1, 97, 3], dict(T=3)), ([33], dict(T=4)),
], ids=_eva_case_id)
def test_eva_attention_parity(positions, shape):
    """The walk of a slot's live window and summary blocks vs the gather
    of its whole table under the visibility mask.  An idle slot reads
    nothing in the kernel (zeros; the oracle averages the trash block)
    and the engine discards both: not compared."""
    from deepspeed_tpu.kernels.eva import eva_attention_reference

    q, ck, cv, tables, q_pos, kw = _eva_inputs(positions, **shape)
    ref = eva_attention_reference(q, ck, cv, tables, q_pos, **kw)
    with kernel_config(interpret=True):
        out = registry.dispatch("eva_attention", q, ck, cv, tables, q_pos,
                                impl="pallas", **kw)
    assert out.shape == ref.shape and out.dtype == ref.dtype == jnp.float32
    live = np.asarray(positions) >= 0
    atol = 2e-6 if q.dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=atol)
    assert not np.asarray(out)[~live].any()


def test_eva_attention_walks_only_the_live_blocks():
    """Entries outside a slot's two live runs are never fetched: with
    them pointed at blocks of NaN the kernel's output does not change
    (the oracle gathers them, and a masked NaN is still a NaN in its
    weighted sum)."""
    q, ck, cv, tables, q_pos, kw = _eva_inputs([45, -1, 96, 3])
    poison = ck.shape[0] // kw["block_size"]
    bad = jnp.full((kw["block_size"], ck.shape[1]), jnp.nan, ck.dtype)
    ck2, cv2 = jnp.concatenate([ck, bad]), jnp.concatenate([cv, bad])
    with kernel_config(interpret=True):
        run = lambda k, v, t: np.asarray(registry.dispatch(
            "eva_attention", q, k, v, t, q_pos, impl="pallas", **kw))
        want = run(ck, cv, tables)
        got = run(ck2, cv2, jnp.where(tables == 0, poison, tables))
    assert np.array_equal(want, got)


_EVA_INFO = dict(q_len=1, block_size=16, table_width=192, window=2048,
                 chunk=16, num_heads=32, head_dim=128, kv_mode="dense",
                 kv_itemsize=2)


@pytest.mark.parametrize("change,why", [
    ({}, None),
    (dict(q_len=2), None),
    (dict(q_len=8), "8 x 32 score rows of 4096 lanes"),
    (dict(q_len=1024), "q_len 1024 is a prefill chunk"),
    (dict(block_size=8, chunk=8), "a block of 8 rows is not whole tiles"),
    (dict(kv_itemsize=4, block_size=8, chunk=8), None),
    (dict(kv_mode="int8"), "int8 rows"),
    (dict(window=2040), "are not whole blocks of 16"),
    (dict(chunk=256), "its 8 summary rows are not whole blocks"),
], ids=lambda v: _paged_case_id(v) if isinstance(v, dict) else "")
def test_eva_attention_shape_rule(change, why, native):
    """What the call site can see decides (serving/layers.py::eva_info):
    the decode program of EvaByte's cell takes the kernel on the chip,
    its prefill and every shape the walk cannot copy take the oracle and
    say why when the kernel is forced."""
    info = dict(_EVA_INFO, **change)
    if why is None:
        assert resolve_impl("eva_attention", info=info) == "pallas"
        return
    assert resolve_impl("eva_attention", info=info) == "jnp"
    with pytest.raises(RuntimeError, match=why):
        resolve_impl("eva_attention", impl="pallas", info=info)


def test_flash_attention_parity():
    from deepspeed_tpu.ops.transformer.attention import xla_attention

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 128), jnp.float32)
               for _ in range(3))
    ref = xla_attention(q, k, v, causal=True)
    with kernel_config(interpret=True):
        out = registry.dispatch("flash_attention", q, k, v,
                                impl="pallas", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6)


def test_sparse_attention_module_auto_matches_oracle_off_tpu():
    """Satellite 1: SparseSelfAttention's selection now routes through
    the registry — auto off-TPU is the jnp oracle BIT-for-bit, and the
    legacy impl="xla" spelling aliases to it."""
    from deepspeed_tpu.ops.sparse_attention import (DenseSparsityConfig,
                                                    SparseSelfAttention)
    from deepspeed_tpu.ops.sparse_attention.sparse_attention import \
        block_sparse_attention

    if ON_TPU:
        pytest.skip("auto selects the kernel on TPU")
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
               for _ in range(3))
    cfg = DenseSparsityConfig(num_heads=2, block=64)
    layout = cfg.make_layout(128)
    ref = block_sparse_attention(q, k, v, layout, 64)
    for impl in ("auto", "xla"):
        mod = SparseSelfAttention(cfg, impl=impl)
        out = mod(q, k, v)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), impl


# ---------------------------------------------------------------------------
# selection contract: config-time naming, forced pallas, counters
# ---------------------------------------------------------------------------


def test_unknown_op_raises_at_config_time_naming_valid_set():
    with pytest.raises(ValueError) as e:
        with kernel_config(ops={"flash_atention": "pallas"}):
            pass
    for name in sorted(KERNEL_OPS):
        assert name in str(e.value)
    with pytest.raises(ValueError, match="must be one of"):
        with kernel_config(impl="triton"):
            pass
    with pytest.raises(ValueError, match="must be one of"):
        with kernel_config(ops={"quant_codec": "triton"}):
            pass
    assert get_kernel_config() == registry.KernelConfig()


def test_dispatch_unknown_op_names_valid_set():
    with pytest.raises(ValueError) as e:
        registry.dispatch("nope", 1)
    assert "quant_codec" in str(e.value)
    with pytest.raises(ValueError, match="unknown variant"):
        registry.dispatch("quant_codec", 1, variant="encode")


@pytest.mark.skipif(ON_TPU, reason="forced pallas is legal on TPU")
def test_forced_pallas_off_tpu_raises_without_interpret_escape():
    x = jnp.zeros((256,), jnp.float32)
    with kernel_config(impl="pallas"):
        with pytest.raises(RuntimeError, match="interpret"):
            registry.dispatch("quant_codec", x, 128, "int8",
                              variant="quantize")
    # the scoped escape runs the kernel under the interpreter
    with kernel_config(impl="pallas", interpret=True):
        p, s = registry.dispatch("quant_codec", x, 128, "int8",
                                 variant="quantize")
    assert p.shape[-1] == 128
    # ... and the call-site escape preserves SparseSelfAttention's
    # historical impl="pallas"-on-CPU behaviour
    assert resolve_impl("quant_codec", "quantize", impl="pallas",
                        interpret_ok=True) == "pallas"


# -- the routed product of a call of few rows (moe/dropless.py) ---------------


def _routed_inputs(T, E, k, live, share=None, dtype=jnp.float32, D=128,
                   F=256, scoring="softmax", push=None, seed=0):
    """A call of T rows over E held experts: (x, experts, weights, idx,
    held, live).  `share` = (first, total): the E held are a share of
    `total` the router chose among; `push` {expert: score} steers every
    row's choices."""
    from deepspeed_tpu.moe import dropless

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    mk = lambda key, shape: (jax.random.normal(key, shape) * 0.1
                             ).astype(dtype)
    experts = {"gate": mk(ks[0], (E, D, F)), "up": mk(ks[1], (E, D, F)),
               "down": mk(ks[2], (E, F, D))}
    first, total = share or (0, E)
    x = jax.random.normal(ks[3], (T, D))
    router = jax.random.normal(ks[4], (D, total)) * 0.1
    if push:
        x = x.at[:, 0].set(30.0)
        router = router.at[0].set(0.0)
        for e, score in push.items():
            router = router.at[0, e].set(score)
    weights, idx = dropless.route(x, router, k, scoring=scoring,
                                  renormalize=scoring == "sigmoid")
    held = None
    if share:
        weights, idx, held = dropless.held_assignments(weights, idx, first, E)
    return x, experts, weights, idx, held, jnp.asarray(live)


_ROUTED_SCENES = {
    # 32 rows of 6 over 16 experts, all live: every expert touched
    "all_experts_touched": dict(T=32, E=16, k=6, live=[True] * 32,
                                touched=16),
    # two live rows of 2: a few
    "a_few_touched": dict(T=16, E=16, k=2, live=[True, True] + [False] * 14,
                          touched=(2, 4)),
    "none_touched": dict(T=16, E=8, k=2, live=[False] * 16, touched=0),
    # the live row is steered to experts 0 and 1; the dead rows' own
    # choices cover the rest and must touch nothing
    "dead_rows_would_touch_more": dict(
        T=16, E=8, k=2, live=[True] + [False] * 15, touched=(1, 2)),
    # Command A+'s routing at toy size: 16 of 128 held, 8 a row by
    # renormalised sigmoid scores, 5 of 16 rows live
    "a_held_share": dict(T=16, E=16, k=8, share=(32, 128),
                         scoring="sigmoid", live=[True] * 5 + [False] * 11,
                         touched=(1, 16)),
    # nothing a live row chose is held here
    "a_held_share_none_here": dict(
        T=16, E=4, k=2, share=(4, 8), scoring="sigmoid",
        push={0: 9.0, 1: 9.0}, live=[True] * 16, touched=0),
    "bf16_T32": dict(T=32, E=8, k=2, dtype=jnp.bfloat16,
                     live=[True] * 12 + [False] * 20, touched=(2, 8)),
    "bf16_T16_column_tiles": dict(T=16, E=8, k=2, dtype=jnp.bfloat16,
                                  live=[True] * 5 + [False] * 11,
                                  touched=(2, 8),
                                  tile_bytes=6 * 128 * 128 * 2),
    # rows that are no whole tile: padded inside the call
    "T3": dict(T=3, E=8, k=2, live=[True, False, True], touched=(2, 4)),
}


@pytest.mark.parametrize("scene", list(_ROUTED_SCENES))
def test_touched_experts_parity(scene, monkeypatch):
    """The walk of the touched list vs `experts_masked` with the dead
    rows' weights zeroed: the same sum, whatever is touched."""
    from deepspeed_tpu.kernels import moe_kernels
    from deepspeed_tpu.moe import dropless

    kw = dict(_ROUTED_SCENES[scene])
    touched, tile_bytes = kw.pop("touched"), kw.pop("tile_bytes", None)
    x, experts, weights, idx, held, live = _routed_inputs(**kw)
    E = experts["gate"].shape[0]
    if tile_bytes:
        monkeypatch.setattr(moe_kernels, "_TOUCHED_TILE_BYTES", tile_bytes)
        assert moe_kernels.touched_tile(128, 256, 2) == 128
    if "dead_rows" in scene:
        all_live = int(dropless.experts_touched(
            idx, jnp.ones_like(live), E, held))
        assert all_live > 2
    n = int(dropless.experts_touched(idx, live, E, held))
    lo, hi = touched if isinstance(touched, tuple) else (touched, touched)
    assert lo <= n <= hi, n
    want = dropless.experts_masked(
        x, experts, jnp.where(live[:, None], weights, 0.0), idx)
    with kernel_config(ops={"touched_experts": "pallas"}, interpret=True):
        got = registry.dispatch(
            "touched_experts", x, experts,
            dropless.combine_weights(
                jnp.where(live[:, None], weights, 0.0), idx, E),
            *dropless.touched_list(idx, live, E, held))
        via = dropless.experts_touched_only(x, experts, weights, idx, live,
                                            held)
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(via))
    # bf16: a float32 sum taken in another order rounds a gated product
    # to the neighbouring bf16 now and then
    tol = 2e-6 if experts["gate"].dtype == jnp.float32 else 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol * max(np.abs(want).max(), 1.0))
    assert not np.asarray(got)[~np.asarray(live)].any()
    if n == 0:
        assert not np.asarray(got).any()


@pytest.mark.parametrize("scene", ["a_few_touched", "a_held_share",
                                   "none_touched", "all_experts_touched"])
def test_touched_list_is_what_experts_touched_counts(scene):
    """One computation feeds the product and the counter: the list's
    count is `experts_touched`, its first `n` entries are the touched
    experts in ascending order and its tail repeats the last."""
    from deepspeed_tpu.moe import dropless

    kw = dict(_ROUTED_SCENES[scene])
    kw.pop("touched")
    _, experts, _, idx, held, live = _routed_inputs(**kw)
    E = experts["gate"].shape[0]
    ids, n = dropless.touched_list(idx, live, E, held)
    ids, n = np.asarray(ids), int(n)
    assert n == int(dropless.experts_touched(idx, live, E, held))
    on = np.asarray(live)[:, None] & (
        np.ones(idx.shape, bool) if held is None else np.asarray(held))
    want = sorted(set(np.asarray(idx)[on].tolist()))
    assert ids[:n].tolist() == want and len(ids) == E
    assert (ids[n:] == (want[-1] if want else 0)).all()


def test_touched_experts_reads_only_the_touched_experts():
    """An expert no live row chose is never fetched: with its matrices
    NaN the kernel's output does not change (the oracle multiplies them
    by a weight of 0, and 0 x NaN is NaN)."""
    from deepspeed_tpu.moe import dropless

    kw = dict(_ROUTED_SCENES["a_few_touched"])
    kw.pop("touched")
    x, experts, weights, idx, held, live = _routed_inputs(**kw)
    E = experts["gate"].shape[0]
    ids, n = dropless.touched_list(idx, live, E, held)
    untouched = np.setdiff1d(np.arange(E), np.asarray(ids)[:int(n)])
    assert len(untouched) >= E - 4
    poisoned = {k: v.at[untouched].set(jnp.nan) for k, v in experts.items()}
    with kernel_config(ops={"touched_experts": "pallas"}, interpret=True):
        run = lambda ex: np.asarray(dropless.experts_touched_only(
            x, ex, weights, idx, live, held))
        want, got = run(experts), run(poisoned)
    assert np.isfinite(got).all() and np.array_equal(want, got)
    assert np.isnan(np.asarray(dropless.experts_masked(
        x, poisoned, jnp.where(live[:, None], weights, 0.0), idx))).any()


_TOUCHED_INFO = dict(tokens=32, num_experts=64, model_dim=2048,
                     expert_dim=1408, itemsize=2)


@pytest.mark.parametrize("change,why", [
    ({}, None),                                    # chatgen's decode
    (dict(tokens=16, num_experts=16, model_dim=4096, expert_dim=4096),
     None),                                        # mixedlen's decode
    (dict(tokens=1), None),
    (dict(tokens=128), None),
    (dict(tokens=129), "129 rows are over the ridge"),
    (dict(tokens=512), "512 rows are over the ridge"),
    (dict(model_dim=64, expert_dim=32), "rows of 64 values"),
    (dict(model_dim=65536, expert_dim=1408), "fits the kernel's VMEM"),
], ids=lambda v: _paged_case_id(v) if isinstance(v, dict) else "")
def test_touched_experts_shape_rule(change, why, native):
    """What the call can see decides (moe/dropless.py::touched_info):
    on the chip a decode step of either routed family takes the kernel,
    a prefill chunk and widths the kernel cannot tile do not, and say
    why when it is forced."""
    info = dict(_TOUCHED_INFO, **change)
    if why is None:
        assert resolve_impl("touched_experts", info=info) == "pallas"
        return
    assert resolve_impl("touched_experts", info=info) == "jnp"
    with pytest.raises(RuntimeError, match=why):
        resolve_impl("touched_experts", impl="pallas", info=info)


def _traces_a_kernel(fn, *shapes) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*shapes))


def _training_attention(**kw):
    from deepspeed_tpu.ops.transformer.attention import multihead_attention

    qkv = (jax.ShapeDtypeStruct((4, 1024, 25, 64), jnp.bfloat16),) * 3
    return _traces_a_kernel(
        lambda q, k, v: multihead_attention(q, k, v, causal=True, **kw),
        *qkv)


@pytest.mark.parametrize("name", sorted(KERNEL_OPS))
def test_env_gate_disables_every_op(name, native, monkeypatch):
    """`DS_KERNEL_<OP>=0` is the one override an operator has, and it
    holds for every op: `auto` takes the oracle and a forced kernel
    raises the reason.  For training attention the gate is read where a
    step reads it, through `multihead_attention`."""
    op = KERNEL_OPS[name]
    variant = op.VARIANTS[0]
    selectable = op.is_compatible()   # moe: no kernel for the chip
    if name == "flash_attention":
        assert _training_attention()
    monkeypatch.setenv(f"DS_KERNEL_{name.upper()}", "0")
    assert not op.is_compatible()
    assert f"DS_KERNEL_{name.upper()}=0" in op.compatibility_message()
    assert resolve_impl(name, variant) == "jnp"
    with pytest.raises(RuntimeError, match="disabled via DS_KERNEL_"):
        resolve_impl(name, variant, impl="pallas")
    if name == "flash_attention":
        assert not _training_attention()
    monkeypatch.delenv(f"DS_KERNEL_{name.upper()}")
    assert op.is_compatible() == selectable


def test_training_attention_dispatches_through_registry(native):
    """`multihead_attention` is a registry call like any other: the
    trace-time counters move, and the scoped override reaches it."""
    snap = COUNTERS.snapshot()
    assert _training_attention()
    d = COUNTERS.delta_since(snap)
    assert d.get("kernel.dispatches", {}).get("calls", 0) == 1
    assert "kernel.fallbacks" not in d

    snap = COUNTERS.snapshot()
    with kernel_config(ops={"flash_attention": "jnp"}):
        assert not _training_attention()
        assert _training_attention(impl="pallas")  # the call site's wins
    d = COUNTERS.delta_since(snap)
    assert d.get("kernel.fallbacks", {}).get("calls", 0) == 1
    assert d.get("kernel.dispatches", {}).get("calls", 0) == 1


def test_initialize_installs_no_kernel_state():
    """An engine built between two traces does not change what the
    second selects — two `initialize()` calls and a `ServeEngine` in one
    process leave the registry as they found it."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config
    from deepspeed_tpu.serving import ServeConfig, ServeEngine

    x = jnp.zeros((256,), jnp.float32)

    def selected():
        snap = COUNTERS.snapshot()
        with kernel_config(ops={"quant_codec": "pallas"}, interpret=True):
            inside = get_kernel_config()
            jax.make_jaxpr(lambda a: registry.dispatch(
                "quant_codec", a, 128, "int8", variant="quantize"))(x)
        d = COUNTERS.delta_since(snap)
        return inside, d.get("kernel.dispatches", {}).get("calls", 0)

    before = selected()
    base = get_kernel_config()
    model = GPT(gpt2_config("nano", max_seq_len=32))
    cfg = {"train_batch_size": 8, "optimizer": {"type": "Adam"},
           "steps_per_print": 0}
    for _ in range(2):
        deepspeed_tpu.initialize(model=model, config=dict(cfg))
        assert get_kernel_config() == base
    params = model.init(jax.random.PRNGKey(0))
    ServeEngine(model, params, ServeConfig(
        block_size=4, max_batch=2, max_seq_len=32, prefill_chunk=8,
        num_blocks=17))
    assert get_kernel_config() == base == registry.KernelConfig()
    assert selected() == before


def test_dispatch_counters_and_off_switch():
    x = jnp.zeros((256,), jnp.float32)
    snap = COUNTERS.snapshot()
    with kernel_config(impl="jnp"):
        registry.dispatch("quant_codec", x, 128, "int8",
                          variant="quantize")
    d = COUNTERS.delta_since(snap)
    assert d.get("kernel.fallbacks", {}).get("calls", 0) == 1

    snap = COUNTERS.snapshot()
    with kernel_config(impl="pallas", interpret=True):
        registry.dispatch("quant_codec", x, 128, "int8",
                          variant="quantize")
    d = COUNTERS.delta_since(snap)
    assert d.get("kernel.dispatches", {}).get("calls", 0) == 1


def test_kernel_config_context_restores():
    base = get_kernel_config()
    with kernel_config(impl="jnp") as cfg:
        assert cfg.impl == "jnp"
        with kernel_config(ops={"moe_dispatch": "pallas"},
                           interpret=True) as inner:
            assert inner.impl_for("moe_dispatch") == "pallas"
        assert get_kernel_config().impl == "jnp"
    assert get_kernel_config() == base
    # ... on an exception too: nothing for conftest to reset
    with pytest.raises(RuntimeError, match="boom"):
        with kernel_config(impl="pallas", interpret=True):
            raise RuntimeError("boom")
    assert get_kernel_config() == base


# ---------------------------------------------------------------------------
# surfaces: ds_report, probe report
# ---------------------------------------------------------------------------


def test_probe_report_covers_every_op():
    rows = probe_report()
    assert [r[0] for r in rows] == sorted(KERNEL_OPS)
    for _name, verdict, reason in rows:
        if ON_TPU:
            assert verdict == "pallas" and reason == ""
        else:
            assert verdict == "jnp-fallback" and "tpu" in reason


def test_ds_report_kernels_section():
    from deepspeed_tpu.env_report import kernel_report

    buf = io.StringIO()
    kernel_report(out=buf)
    text = buf.getvalue()
    assert "kernel op" in text
    for name in KERNEL_OPS:
        assert name in text
    if not ON_TPU:
        assert "jnp-fallback" in text
