"""The Pallas kernel registry (deepspeed_tpu/kernels/).

THE acceptance pins, per ISSUE 18:

* every registered op's Pallas kernel matches its jnp oracle ON CPU
  (the kernel runs under the Pallas interpreter there) — BIT-exact for
  the quant codec (both wires, both directions, non-finite markers
  included) and the MoE dispatch permutation; tolerance-bounded for
  attention and the MoE combine (reduction-order / FMA rounding);
* an unknown op name fails at CONFIG time naming the registered set,
  never inside a traced program;
* `impl="pallas"` forced off-TPU raises loudly unless the interpret
  escape is set;
* `kernel.dispatches` / `kernel.fallbacks` count every resolution;
* the autotuner's `kernel` scope enumerates per-op pins through the
  REAL `DeepSpeedKernelsConfig` validator (invalid points pruned and
  counted, never probed) and its fabric-keyed winner table overrides
  the auto heuristic only while the fabric still matches;
* `tools/kernel_bench.py --dry-run` runs every parity lane and records
  a durable artifact.
"""

import io
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.kernels import (KERNEL_OPS, KernelConfig, clear_winners,
                                   get_kernel_config, kernel_config,
                                   parse_kernels_config, probe_report,
                                   record_winner, registry, resolve_impl,
                                   winner_for)
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
                  lengths=None, dead_to_trash=False):
    """A pool `[rows, pool_width(H, Dh)]`, tables [R, W] and query
    positions [R, T].  `lengths` [R]: what each slot holds once this
    call's rows are written (0: the slot is idle, its positions -1);
    None draws them.  `dead_to_trash`: table entries past a slot's
    length point at the trash block, as the allocator pads them."""
    from deepspeed_tpu.runtime.comm.quant import quantize_rows
    from deepspeed_tpu.serving.kv_cache import pool_rows

    rng = np.random.RandomState(seed)
    nblocks = R * W + 1

    def pool():
        c = jnp.asarray(rng.randn(nblocks * bs, H, Dh), jnp.float32)
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


def _paged_both(kv_mode, **kw):
    from deepspeed_tpu.kernels.paged import paged_attention_reference

    q, ck, cv, tables, q_pos, bs = _paged_inputs(kv_mode, **kw)
    ref = paged_attention_reference(q, ck, cv, tables, q_pos,
                                    kv_mode=kv_mode, block_size=bs)
    with kernel_config(interpret=True):
        out = registry.dispatch("paged_attention", q, ck, cv, tables,
                                q_pos, variant="default", impl="pallas",
                                kv_mode=kv_mode, block_size=bs)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    return np.asarray(out, np.float32), np.asarray(ref, np.float32), q_pos


# slots of length 1, one full block, one block plus one row, the full
# table, and an idle slot (bs 4, W 4)
_EDGE_LENGTHS = [1, 4, 5, 16, 0]


def _paged_case_id(v):
    if not isinstance(v, dict):
        return str(v)
    return "_".join(
        f"{k}{'x'.join(map(str, x)) if isinstance(x, list) else x}"
        for k, x in v.items()) or "H2_Dh128"


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


def test_paged_attention_slot_ignores_the_other_slots():
    """Batching invariance of the kernel: a slot's output is the same
    bits whatever the other slots hold — long, short or idle."""
    kw = dict(H=25, Dh=64, R=3, bs=4, W=8, dead_to_trash=True)
    a, _, _ = _paged_both("dense", lengths=[13, 32, 7], **kw)
    b, _, _ = _paged_both("dense", lengths=[13, 1, 0], **kw)
    assert np.array_equal(a[0], b[0])


def test_flash_attention_parity():
    from deepspeed_tpu.kernels.flash import flash_attention_reference

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 128), jnp.float32)
               for _ in range(3))
    ref = flash_attention_reference(q, k, v, causal=True)
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
        parse_kernels_config({"ops": {"flash_atention": "pallas"}})
    for name in sorted(KERNEL_OPS):
        assert name in str(e.value)

    from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                              DeepSpeedKernelsConfig)

    with pytest.raises(DeepSpeedConfigError, match="registered ops"):
        DeepSpeedKernelsConfig({"kernels": {"ops": {"nope": "jnp"}}})
    with pytest.raises(ValueError, match="unknown key"):
        parse_kernels_config({"implementation": "pallas"})
    with pytest.raises(ValueError, match="must be one of"):
        parse_kernels_config({"impl": "triton"})


def test_dispatch_unknown_op_names_valid_set():
    with pytest.raises(ValueError) as e:
        registry.dispatch("nope", 1)
    assert "quant_codec" in str(e.value)
    with pytest.raises(ValueError, match="unknown variant"):
        registry.dispatch("quant_codec", 1, variant="encode")


def test_full_config_round_trip_and_engine_install():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig(
        {"train_batch_size": 8,
         "kernels": {"impl": "auto", "ops": {"quant_codec": "jnp"},
                     "counters": False}}, world_size=1)
    kc = cfg.kernels_config.config
    assert kc == KernelConfig(impl="auto", ops={"quant_codec": "jnp"},
                              counters=False)
    assert kc.impl_for("quant_codec") == "jnp"
    assert kc.impl_for("flash_attention") == "auto"

    from deepspeed_tpu.runtime.config import DeepSpeedConfigError

    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "kernels": {"ops": {"bogus": "pallas"}}},
                        world_size=1)


@pytest.mark.skipif(ON_TPU, reason="forced pallas is legal on TPU")
def test_forced_pallas_off_tpu_raises_without_interpret_escape():
    x = jnp.zeros((256,), jnp.float32)
    with kernel_config(impl="pallas"):
        with pytest.raises(RuntimeError, match="interpret"):
            registry.dispatch("quant_codec", x, 128, "int8",
                              variant="quantize")
    # the config-level escape runs the kernel under the interpreter
    with kernel_config(impl="pallas", interpret=True):
        p, s = registry.dispatch("quant_codec", x, 128, "int8",
                                 variant="quantize")
    assert p.shape[-1] == 128
    # ... and the call-site escape preserves SparseSelfAttention's
    # historical impl="pallas"-on-CPU behaviour
    assert resolve_impl("quant_codec", "quantize", impl="pallas",
                        interpret_ok=True) == "pallas"


def test_env_switch_disables_native_selection(monkeypatch):
    monkeypatch.setenv("DS_KERNEL_QUANT_CODEC", "0")
    op = KERNEL_OPS["quant_codec"]
    assert not op.is_compatible()
    assert "DS_KERNEL_QUANT_CODEC=0" in op.compatibility_message()


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

    snap = COUNTERS.snapshot()
    with kernel_config(impl="jnp", counters=False):
        registry.dispatch("quant_codec", x, 128, "int8",
                          variant="quantize")
    d = COUNTERS.delta_since(snap)
    assert "kernel.fallbacks" not in d and "kernel.dispatches" not in d


def test_kernel_config_context_restores():
    base = get_kernel_config()
    with kernel_config(impl="jnp") as cfg:
        assert cfg.impl == "jnp"
        with kernel_config(ops={"moe_dispatch": "pallas"},
                           interpret=True) as inner:
            assert inner.impl_for("moe_dispatch") == "pallas"
        assert get_kernel_config().impl == "jnp"
    assert get_kernel_config() == base


# ---------------------------------------------------------------------------
# autotune kernel scope + winner table
# ---------------------------------------------------------------------------


def test_generate_kernel_candidates_through_real_validator():
    from deepspeed_tpu.runtime.autotune.space import (
        generate_kernel_candidates, knob_distance, neighborhood)

    cands, rejected = generate_kernel_candidates()
    assert rejected == 0
    assert len(cands) == 2 * len(KERNEL_OPS)
    names = {c.name for c in cands}
    assert "kern_quant_codec_pallas" in names
    for c in cands:
        assert c.scope == "kernel"
        # safe only for the bit-exact codec
        assert c.safe_numerics == (c.name.startswith("kern_quant_codec"))

    # invalid op names / impl values are PRUNED and counted, not raised
    cands2, rejected2 = generate_kernel_candidates(
        op_names=["quant_codec", "not_an_op"],
        impls=("pallas", "jnp", "triton"))
    assert [c.name for c in cands2] == ["kern_quant_codec_pallas",
                                        "kern_quant_codec_jnp"]
    assert rejected2 == 4

    # distance: same op differing pin = 1; different ops = 2 (both
    # differ from auto); radius-1 neighborhood is the same-op flip
    a = next(c for c in cands if c.name == "kern_quant_codec_pallas")
    b = next(c for c in cands if c.name == "kern_quant_codec_jnp")
    m = next(c for c in cands if c.name == "kern_moe_dispatch_pallas")
    assert knob_distance(a, b) == 1
    assert knob_distance(a, m) == 2
    assert [c.name for c in neighborhood(a, cands, radius=1)] == \
        ["kern_quant_codec_jnp"]
    assert "quant_codec=pallas" in a.describe()


def test_kernel_scope_disjoint_from_train_and_serve_spaces():
    from deepspeed_tpu.runtime.autotune.space import (
        generate_candidates, generate_kernel_candidates,
        generate_serve_candidates, knob_distance)

    kern = generate_kernel_candidates()[0][0]
    train = generate_candidates(8)[0][0]
    serve = generate_serve_candidates(64)[0][0]
    far = knob_distance(train, serve)
    assert knob_distance(kern, train) == far
    assert knob_distance(kern, serve) == far
    assert far > max(knob_distance(kern, k2)
                     for k2 in generate_kernel_candidates()[0])


def test_winner_table_fabric_keyed():
    from deepspeed_tpu.runtime.autotune.fingerprint import \
        kernel_fingerprint

    clear_winners()
    try:
        with pytest.raises(ValueError):
            record_winner("nope", "pallas")
        with pytest.raises(ValueError):
            record_winner("quant_codec", "triton")

        fp = kernel_fingerprint("quant_codec", shape=(1024,))
        record_winner("quant_codec", "jnp", fingerprint=fp)
        assert winner_for("quant_codec") == "jnp"
        # a jnp winner pins the oracle even where auto would probe
        assert resolve_impl("quant_codec", "quantize") == "jnp"

        # same winner recorded on a DIFFERENT fabric no longer applies
        stale = dict(fp, fabric=dict(fp["fabric"], backend="other"))
        record_winner("quant_codec", "jnp", fingerprint=stale)
        assert winner_for("quant_codec") is None

        # a pallas winner never forces the kernel off its fabric
        record_winner("moe_dispatch", "pallas", fingerprint=fp)
        expect = "pallas" if ON_TPU else "jnp"
        assert resolve_impl("moe_dispatch", "dispatch") == expect
    finally:
        clear_winners()


# ---------------------------------------------------------------------------
# surfaces: ds_report, probe report, bench dry-run
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


def test_kernel_bench_dry_run(tmp_path):
    import importlib
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        bench = importlib.import_module("kernel_bench")
    finally:
        sys.path.pop(0)
    result = bench.run_dry(str(tmp_path))
    assert result["unit"] == "parity_lanes" and result["value"] == 11
    for lane in ("flash_attention", "sparse_attention",
                 "paged_attention_dense", "paged_attention_int8",
                 "paged_attention_int4", "quant_codec_quantize_int8",
                 "quant_codec_dequantize_int4", "moe_dispatch",
                 "moe_combine"):
        assert lane in result, lane
    assert result["quant_codec_quantize_int8"]["parity"] == "bitwise"
    assert result["moe_combine"]["parity"] == "tolerance"
    pins = result["counters"]
    assert pins["forced_pallas"] == {"dispatches": 11, "fallbacks": 0}
    if not ON_TPU:
        assert pins["auto"] == {"dispatches": 0, "fallbacks": 11}
    # the artifact landed through monitor/artifacts.py
    assert (tmp_path / "manifest.jsonl").exists()
    assert list(tmp_path.glob("*_kernel_registry_dryrun.json"))
