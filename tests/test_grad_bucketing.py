"""Bucketed gradient-reduction wire: BucketPlan layout, parity of the
bucketed vs implicit wires across all three jitted step paths, wire-byte
accounting pinned EXACTLY against the plan, and the reference
`allreduce_gradients` surface (runtime/comm/bucketing.py + engine)."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.runtime.comm.bucketing import BucketPlan
from tests.simple_model import SimpleModel, random_batches


def _make_engine(comm=None, stage=0, gas=1, **cfg_extra):
    cfg = {
        "train_batch_size": 32 * gas,
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage},
        "mesh": {"data": 8},
        "steps_per_print": 0,
    }
    if comm is not None:
        cfg["comm"] = comm
    cfg.update(cfg_extra)
    engine, *_ = ds.initialize(model=SimpleModel(), config_params=cfg)
    return engine


BUCKETED = {"gradient_reduction": "bucketed", "reduce_bucket_size": 128}


# ---------------------------------------------------------------------------
# BucketPlan layout
# ---------------------------------------------------------------------------

def test_plan_layout_dtype_segregation_and_caps():
    tree = {
        "a": jax.ShapeDtypeStruct((10, 10), jnp.float32),   # 100
        "b": jax.ShapeDtypeStruct((60,), jnp.float32),      # 60
        "c": jax.ShapeDtypeStruct((10,), jnp.bfloat16),     # 10
        "d": jax.ShapeDtypeStruct((50,), jnp.float32),      # 50
    }
    plan = BucketPlan(tree, dp_size=8, bucket_elems=128, wire="fp32")
    assert plan.n_leaves == 4 and plan.total_elems == 220
    by_dtype = {}
    for b in plan.buckets:
        by_dtype.setdefault(np.dtype(b.dtype).name, []).append(b)
    # bf16 leaf never shares a bucket with fp32 leaves
    assert len(by_dtype["bfloat16"]) == 1
    assert by_dtype["bfloat16"][0].n_elems == 10
    # 100+60 > 128 closes the first fp32 bucket at one leaf; 60+50 packs
    f32_sizes = sorted(b.n_elems for b in by_dtype["float32"])
    assert f32_sizes == [100, 110]
    packed = next(b for b in by_dtype["float32"] if b.n_elems == 110)
    assert [s.offset for s in packed.slots] == [0, 60]
    # wire accounting: every element once, at the wire dtype's width
    assert plan.wire_bytes_per_reduction == 220 * 4
    assert plan.collectives_per_reduction == plan.n_buckets == 3


def test_plan_flatten_unflatten_roundtrip():
    rng = np.random.RandomState(0)
    tree = {"a": jnp.asarray(rng.randn(10, 10), jnp.float32),
            "b": jnp.asarray(rng.randn(60), jnp.float32),
            "d": jnp.asarray(rng.randn(50), jnp.float32)}
    plan = BucketPlan(tree, dp_size=8, bucket_elems=128, wire="fp32",
                      scatter=True)
    buckets = plan.flatten(tree)
    # scatter pads every bucket to a dp multiple with zeros
    for flat, spec in zip(buckets, plan.buckets):
        assert flat.shape == (spec.padded,)
        assert spec.padded % 8 == 0
        if spec.padded > spec.n_elems:
            assert np.all(np.asarray(flat[spec.n_elems:]) == 0)
    back = plan.unflatten(buckets)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))


def test_plan_validation():
    tree = {"a": jax.ShapeDtypeStruct((8,), jnp.float32)}
    with pytest.raises(ValueError, match="wire"):
        BucketPlan(tree, dp_size=2, bucket_elems=16, wire="fp8")
    with pytest.raises(ValueError, match="reduce_bucket_size"):
        BucketPlan(tree, dp_size=2, bucket_elems=0)
    # the split wire is gather-structured: scatter lowers back to gather
    plan = BucketPlan(tree, dp_size=2, bucket_elems=16, wire="split",
                      scatter=True)
    assert plan.scatter is False
    assert plan.wire_bytes_per_reduction == 8 * 3  # fp16 m + int8 e
    assert plan.collectives_per_reduction == 2     # two gathers per bucket


def test_config_surface():
    with pytest.raises(ValueError, match="gradient_reduction"):
        _make_engine(comm={"gradient_reduction": "sometimes"})
    with pytest.raises(ValueError, match="wire_dtype"):
        _make_engine(comm={"gradient_reduction": "bucketed",
                           "wire_dtype": "fp8"})
    # reference fp32_allreduce key forces the fp32 wire
    eng = _make_engine(comm={"gradient_reduction": "bucketed",
                             "wire_dtype": "bf16"}, fp32_allreduce=True)
    assert eng.bucket_plan is not None and eng.bucket_plan.wire == "fp32"
    assert eng.allreduce_always_fp32() is True
    eng = _make_engine(comm={"gradient_reduction": "bucketed",
                             "wire_dtype": "bf16"})
    assert eng.bucket_plan.wire == "bf16"
    assert eng.allreduce_always_fp32() is False
    # reduce_bucket_size falls back to the zero_optimization knob
    eng = _make_engine(comm={"gradient_reduction": "bucketed"},
                       zero_optimization={"stage": 0,
                                          "reduce_bucket_size": 64})
    assert eng.bucket_plan.bucket_elems == 64
    assert eng.bucket_plan.n_buckets > 1


# ---------------------------------------------------------------------------
# parity: bucketed wire vs implicit XLA psum, all three step paths
# ---------------------------------------------------------------------------

def _train(engine, mode, gas, steps=3, seed=3):
    it = random_batches(steps * gas, batch_size=32, seed=seed)
    loss = None
    if mode == "scan":
        for _ in range(steps):
            loss = engine.train_batch(it)
    else:
        for _ in range(steps * gas):
            loss = engine.forward(next(it))
            engine.backward()
            engine.step()
    return float(loss), jax.tree_util.tree_leaves(engine.params)


@pytest.mark.parametrize("stage", [0, 2])
@pytest.mark.parametrize("mode,gas", [("fused", 1), ("scan", 2),
                                      ("micro", 2)])
def test_bucketed_matches_implicit(stage, mode, gas):
    """gas==1 fused, gas>1 full_scan, and the split micro/apply pair all
    produce the same losses and updated params through the bucketed wire
    as through the implicit psum (stage 2 additionally exercises the
    reduce-scatter lowering)."""
    la, pa = _train(_make_engine(stage=stage, gas=gas), mode, gas)
    eng = _make_engine(comm=BUCKETED, stage=stage, gas=gas)
    assert eng.bucket_plan is not None and eng.bucket_plan.n_buckets > 1
    assert eng.bucket_plan.scatter == (stage >= 2)
    lb, pb = _train(eng, mode, gas)
    assert abs(la - lb) < 1e-5
    for x, y in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("wire,rtol", [("bf16", 5e-2), ("split", 1e-2)])
def test_narrow_wires_track_fp32(wire, rtol):
    """bf16 and the 24-bit split wire trade precision for bytes: after a
    few optimizer steps the params stay within the wire's accumulation
    error of the fp32 run (split's fp16 mantissa is the tighter of the
    two)."""
    la, pa = _train(_make_engine(), "fused", 1, steps=4)
    comm = dict(BUCKETED, wire_dtype=wire)
    lb, pb = _train(_make_engine(comm=comm), "fused", 1, steps=4)
    assert abs(la - lb) < 5e-3
    for x, y in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=1e-3)


def test_split_wire_exponent_range_safety():
    """fp32 frexp exponents span [-148, 128] but the split wire carries
    int8: subnormals must flush to zero and the >= 2^127 tail must
    surface as non-finite (so the overflow check fires) — neither may
    WRAP into a silently wrong finite gradient."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm.mesh import DATA_AXIS, make_mesh

    info = make_mesh(data=8)
    vals = np.zeros((8,), np.float32)
    vals[0] = 1e-40    # fp32 subnormal: frexp exponent -132
    vals[1] = 2.5e38   # >= 2^127: frexp exponent 128
    vals[2] = 1.5
    vals[3] = -3e-20
    tree = {"g": jnp.asarray(vals)}
    plan = BucketPlan(tree, dp_size=8, bucket_elems=1024, wire="split")

    def local(t):
        return plan.unflatten(plan.reduce(plan.flatten(t)))

    out = np.asarray(jax.shard_map(
        local, mesh=info.mesh, in_specs=(P(),), out_specs=P(),
        axis_names=info.manual_axes({DATA_AXIS}),
        check_vma=False)(tree)["g"])
    assert out[0] == 0.0, "subnormal must flush, not wrap to ~2^108"
    assert not np.isfinite(out[1]), "2^127 tail must trip overflow"
    np.testing.assert_allclose(out[2], 1.5, rtol=1e-3)
    np.testing.assert_allclose(out[3], -3e-20, rtol=1e-3)
    assert np.all(out[4:] == 0.0)


# ---------------------------------------------------------------------------
# wire-byte accounting (tier-1): COUNTERS must match the plan EXACTLY
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,gas", [("fused", 1), ("scan", 2),
                                      ("micro", 2)])
def test_counter_accounting_matches_plan_exactly(mode, gas):
    """`grad_wire.reduce` deltas == plan-predicted wire bytes/collective
    counts per reduction event, exactly — a silent double-reduction or a
    dropped leaf changes the product and fails here."""
    eng = _make_engine(comm=BUCKETED, gas=gas)
    plan = eng.bucket_plan
    snap = COUNTERS.snapshot()
    steps = 2
    _train(eng, mode, gas, steps=steps)
    delta = COUNTERS.delta_since(snap).get("grad_wire.reduce")
    events = steps * gas  # one reduction per micro batch on every path
    assert delta is not None, "bucketed step recorded no wire bytes"
    assert delta["bytes"] == plan.wire_bytes_per_reduction * events
    assert delta["calls"] == plan.collectives_per_reduction * events


def test_implicit_path_records_no_wire_counters():
    eng = _make_engine()
    snap = COUNTERS.snapshot()
    _train(eng, "fused", 1, steps=2)
    assert "grad_wire.reduce" not in COUNTERS.delta_since(snap)


# ---------------------------------------------------------------------------
# reference API surface: allreduce_gradients + fallbacks
# ---------------------------------------------------------------------------

def test_allreduce_gradients_retunes_bucket_plan():
    eng = _make_engine(comm=BUCKETED)
    assert eng.bucket_plan.bucket_elems == 128
    n0 = eng.bucket_plan.n_buckets
    eng.allreduce_gradients(bucket_size=10_000)
    assert eng.bucket_plan.bucket_elems == 10_000
    assert eng.bucket_plan.n_buckets < n0  # everything fused into one
    # still trains and matches the implicit wire after the retune
    la, pa = _train(_make_engine(), "fused", 1)
    lb, pb = _train(eng, "fused", 1)
    assert abs(la - lb) < 1e-5
    for x, y in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


def test_allreduce_gradients_noop_on_dense_raises_off_path():
    _make_engine().allreduce_gradients()  # implicit in-jit: benign no-op
    onebit, *_ = ds.initialize(
        model=SimpleModel(), config_params={
            "train_batch_size": 32,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-2, "freeze_step": 2}},
            "mesh": {"data": 8},
            "steps_per_print": 0,
        })
    assert getattr(onebit, "_onebit_hot", False)
    with pytest.raises(RuntimeError, match="compressed wire"):
        onebit.allreduce_gradients()


def test_bucketed_request_falls_back_when_ineligible():
    """ZeRO-3 (param sharding) and the 1-bit wire keep the implicit /
    optimizer-owned reduction; the request must degrade loudly-but-safely,
    not break training."""
    eng = _make_engine(comm=BUCKETED, stage=3)
    assert eng.bucket_plan is None
    loss, _ = _train(eng, "fused", 1, steps=2)
    assert np.isfinite(loss)


def test_onebit_dense_fallback_still_gets_buckets():
    """A 1-bit optimizer whose compressed hot path is ineligible (gas>1)
    runs DENSE DP reduction — the bucketed wire must engage there, not
    be blocked by the optimizer's mere capability."""
    eng = _make_engine(comm=BUCKETED, gas=2, optimizer={
        "type": "OneBitAdam",
        "params": {"lr": 1e-2, "freeze_step": 100}})
    assert not getattr(eng, "_onebit_hot", False)
    assert eng.bucket_plan is not None
    loss, _ = _train(eng, "micro", 2, steps=2)
    assert np.isfinite(loss)


# ---------------------------------------------------------------------------
# the real wire: 2-process TCP slow lane
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_process_bucketed_parity():
    """The bucketed wire over a REAL serialization boundary (2
    jax.distributed processes, gloo/TCP): implicit, flat-bucketed, and
    hierarchical (data_outer=2 — one outer group per process, the
    inter-group hop riding the actual TCP boundary) all converge to the
    same loss/params, and all processes agree."""
    nprocs = 2
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    worker = os.path.join(os.path.dirname(__file__), "grad_wire_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), str(nprocs), coord],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for i in range(nprocs)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    lines = [ln for out in outs for ln in out.splitlines()
             if ln.startswith("GWOK")]
    assert len(lines) == nprocs, outs
    # every process saw identical implicit/bucketed results
    assert len({ln.split(" ", 2)[2] for ln in lines}) == 1, lines
    implicit = lines[0].split("implicit=")[1].split()[0]
    bucketed = lines[0].split("bucketed=")[1].split()[0]
    hier = lines[0].split("hier=")[1].split()[0]
    il, ip = map(float, implicit.split("/"))
    bl, bp = map(float, bucketed.split("/"))
    hl, hp = map(float, hier.split("/"))
    assert abs(il - bl) < 1e-4 and abs(ip - bp) / (abs(ip) + 1e-6) < 1e-4
    # the two-level wire (fp32/fp32) must land on the same training
    # trajectory as the flat wires over the real TCP boundary
    assert abs(il - hl) < 1e-4 and abs(ip - hp) / (abs(ip) + 1e-6) < 1e-4
    # the worker asserted the overlapped lanes bitwise against serial
    # (socket exchange over the real TCP boundary)
    assert all("overlap_bitwise=1" in ln for ln in lines), lines
