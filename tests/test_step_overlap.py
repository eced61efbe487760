"""comm.overlap + the schedule-driven step builder.

Covers:
* config validation of the `comm.overlap` knob (typos fail at config
  time naming the key and the valid set);
* LOGGED fallback to the serial path for configurations overlap cannot
  serve (onebit, offload, implicit reduction) — never a silent no-op;
* the host-exchange transport (runtime/comm/overlap.py): ticket
  ordering, threaded materialization, teardown without thread leaks;
* the parity contract: overlapped vs serial training is BIT-identical
  (losses and params) across the step-path matrix x ZeRO stage x
  hierarchy x wire — the combine program mirrors the serial wire's
  reduction math expression for expression, including XLA's
  f32-accumulate-then-round bf16 psum semantics (pinned here);
* qwZ prefetch (stage 3): parity, `qwz.prefetch_hits`, stale-prefetch
  invalidation when params are replaced out of band;
* per-dispatch counters under overlap (`grad_wire.reduce` pinned to the
  plan exactly; `grad_wire.exposed_ms` present) and their rendering by
  monitor/report.py;
* one `resilience.step_boundary` + one StepWatchdog beat per optimizer
  step on EVERY composition the step builder emits (fused / scan /
  split / overlap) — the rebuilt step builder must not double- or
  zero-fire the chaos hooks;
* the grad_wire_bench --overlap CPU dry-run (tier-1 anti-rot).
"""

import logging
import os
import sys
import threading
import time

import numpy as np
import pytest

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.runtime import resilience
from deepspeed_tpu.runtime.comm.overlap import (ExchangeTicket,
                                                LocalExchange)

from tests.simple_model import SimpleModel, random_batches

BASE_COMM = {"gradient_reduction": "bucketed", "reduce_bucket_size": 128}


class _LogCapture(logging.Handler):
    """The deepspeed_tpu logger runs propagate=False, so caplog never
    sees it — attach a handler directly."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record):
        self.records.append(record)


@pytest.fixture
def ds_log():
    lg = logging.getLogger("deepspeed_tpu")
    h = _LogCapture()
    lg.addHandler(h)
    try:
        yield h
    finally:
        lg.removeHandler(h)


def _make(comm=None, stage=0, gas=1, hidden=16, **cfg_extra):
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
    engine, *_ = ds.initialize(model=SimpleModel(hidden_dim=hidden),
                               config_params=cfg)
    return engine


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
    out = (float(loss), [np.asarray(x) for x in
                         jax.tree_util.tree_leaves(engine.params)])
    engine.finalize_monitoring()
    return out


def _assert_bitwise(a, b, ctx=""):
    assert a[0] == b[0], (ctx, a[0], b[0])
    for x, y in zip(a[1], b[1]):
        assert (x == y).all(), (ctx, float(np.abs(x - y).max()))


# ---------------------------------------------------------------------------
# config + fallback
# ---------------------------------------------------------------------------

def test_config_overlap_validation():
    from deepspeed_tpu.runtime.config import parse_comm_overlap

    for raw, want in ((None, "none"), (False, "none"), ("off", "none"),
                      (True, "on"), ("on", "on"), ("true", "on"),
                      ("auto", "auto"), ("NONE", "none")):
        assert parse_comm_overlap(raw) == want, raw
    with pytest.raises(ValueError) as e:
        _make(comm=dict(BASE_COMM, overlap="always"))
    msg = str(e.value)
    assert "overlap" in msg and "always" in msg
    for valid in ("none", "auto", "on"):
        assert valid in msg, msg


def test_overlap_engages_on_bucketed_wire():
    eng = _make(comm=dict(BASE_COMM, overlap="auto"))
    assert eng._overlap_mode == "wire"
    assert "grads" in eng._step_fns and "combine" in eng._step_fns
    assert "full" not in eng._step_fns and "full_scan" not in eng._step_fns
    eng.finalize_monitoring()


def test_overlap_fallback_is_logged_not_silent(ds_log):
    # implicit reduction: nothing to overlap at stage<3
    eng = _make(comm={"overlap": "on"})
    assert eng._overlap_mode is None and "grads" not in eng._step_fns
    assert any("overlap" in r.getMessage() and "serial" in r.getMessage()
               and r.levelno >= logging.WARNING
               for r in ds_log.records), \
        [r.getMessage() for r in ds_log.records]
    eng.finalize_monitoring()


def test_overlap_fallback_offload(ds_log):
    eng = _make(comm=dict(BASE_COMM, overlap="on"), stage=2,
                zero_optimization={"stage": 2,
                                   "offload_optimizer": {
                                       "device": "cpu"}})
    assert eng._overlap_mode is None
    assert any("Offload" in r.getMessage() for r in ds_log.records
               if "overlap" in r.getMessage()), \
        [r.getMessage() for r in ds_log.records]
    eng.finalize_monitoring()


def test_overlap_fallback_onebit(ds_log):
    eng = _make(comm=dict(BASE_COMM, overlap="on"),
                optimizer={"type": "OneBitAdam",
                           "params": {"lr": 1e-2,
                                      "freeze_step": 2}})
    assert eng._overlap_mode is None
    assert any("1-bit" in r.getMessage() for r in ds_log.records
               if "overlap" in r.getMessage()), \
        [r.getMessage() for r in ds_log.records]
    eng.finalize_monitoring()


# ---------------------------------------------------------------------------
# transport unit tests
# ---------------------------------------------------------------------------

def test_ticket_wait_and_timing():
    t = ExchangeTicket(seq=0, world=2)
    t.post(1, np.arange(3, dtype=np.uint8))
    assert not t.ready
    t.post(0, np.zeros(3, dtype=np.uint8))
    assert t.ready and t.done_at is not None
    mat = t.wait()
    assert mat.shape == (2, 3)
    assert (mat[1] == np.arange(3)).all()
    assert t.wait_us >= 0


def test_ticket_timeout_names_missing_ranks():
    t = ExchangeTicket(seq=7, world=2)
    t.post(0, np.zeros(1, np.uint8))
    with pytest.raises(TimeoutError, match="seq=7"):
        t.wait(timeout_s=0.05)


def test_local_exchange_materializes_on_worker_and_closes():
    before = set(threading.enumerate())
    ex = LocalExchange(world=2)
    payloads = [np.full(4, r, np.uint8) for r in range(2)]
    ticket = ex.submit([(r, (lambda p=p: p)) for r, p in
                        enumerate(payloads)])
    mat = ticket.wait()
    assert (mat == np.stack(payloads)).all()
    # submission order == sequence order
    t2 = ex.submit([(r, (lambda p=p: p)) for r, p in
                    enumerate(payloads)])
    assert t2.seq == ticket.seq + 1
    t2.wait()
    ex.close()
    ex.close()  # idempotent
    leaked = [th for th in threading.enumerate()
              if th not in before and th.is_alive()
              and "overlap" in th.name]
    assert not leaked, leaked


def test_worker_error_surfaces_at_wait():
    ex = LocalExchange(world=2)
    ticket = ex.submit([(0, lambda: np.zeros(1, np.uint8))])  # missing rank
    with pytest.raises(RuntimeError, match="failed"):
        ticket.wait(timeout_s=5)
    ex.close()


# ---------------------------------------------------------------------------
# psum association contract (the bit-parity foundation)
# ---------------------------------------------------------------------------

def test_psum_matches_ordered_fold_fp32_and_bf16():
    """The combine program's fold mirrors what XLA:CPU's psum actually
    lowers to: a rank-ordered linear sum, with bf16 accumulating at f32
    width and rounding the RESULT.  If a jax upgrade changes either,
    this pins the break to the cause instead of a parity-test shrug."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    rng = np.random.RandomState(0)
    x = (rng.randn(8, 513) * rng.uniform(0.1, 100, (8, 1))).astype(
        np.float32)

    def psum_of(v):
        return jax.jit(jax.shard_map(
            lambda s: jax.lax.psum(s, "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P(), axis_names={"data"},
            check_vma=False))(v)

    got = np.asarray(psum_of(jnp.asarray(x)))
    fold = x[0]
    for r in range(1, 8):
        fold = fold + x[r]
    assert (got == fold).all()

    xb = jnp.asarray(x).astype(jnp.bfloat16)
    got_b = np.asarray(psum_of(xb).astype(jnp.float32))
    want_b = np.asarray(
        jnp.sum(xb.astype(jnp.float32), axis=0).astype(jnp.bfloat16)
        .astype(jnp.float32))
    assert (got_b == want_b).all()


# ---------------------------------------------------------------------------
# parity: overlapped vs serial is bitwise across the matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire,stage,hier,mode,gas", [
    # step-path matrix: fused (gas1 forward), scan (train_batch), split
    # (manual micro loop) x stage {0,2} x hierarchy {none,2,auto} x
    # wire {fp32,bf16,int8} — rotated so every axis value appears
    ("fp32", 0, None, "fused", 1),
    ("fp32", 2, {"outer": 2}, "scan", 2),
    ("bf16", 0, "auto", "micro", 2),
    ("bf16", 2, None, "fused", 1),
    ("int8", 0, {"outer": 2}, "micro", 2),
    ("int8", 2, "auto", "scan", 2),
    ("split", 0, None, "micro", 2),
    ("int4", 2, {"outer": 2}, "fused", 1),
])
def test_overlap_bitwise_parity(wire, stage, hier, mode, gas):
    key = ("wire_dtype_outer" if hier is not None and wire != "fp32"
           else "wire_dtype")
    comm = dict(BASE_COMM, **{key: wire})
    if hier is not None:
        comm["hierarchy"] = hier
    serial = _train(_make(comm=dict(comm, overlap="none"), stage=stage,
                          gas=gas), mode, gas)
    snap = COUNTERS.snapshot()
    eng = _make(comm=dict(comm, overlap="auto"), stage=stage, gas=gas)
    assert "grads" in eng._step_fns, (wire, stage, hier)
    overlapped = _train(eng, mode, gas)
    deltas = COUNTERS.delta_since(snap)
    _assert_bitwise(serial, overlapped, ctx=(wire, stage, hier, mode))
    assert "grad_wire.exposed_ms" in deltas, deltas.keys()
    assert deltas["grad_wire.exposed_ms"]["calls"] == 3  # one per step


def test_overlap_counters_pin_to_plan_exactly():
    gas, steps = 2, 3
    snap = COUNTERS.snapshot()
    eng = _make(comm=dict(BASE_COMM, overlap="auto", wire_dtype="int8"),
                gas=gas)
    plan = eng.bucket_plan
    _train(eng, "micro", gas, steps=steps)
    d = COUNTERS.delta_since(snap)
    wire = d["grad_wire.reduce"]
    assert wire["bytes"] == plan.wire_bytes_per_reduction * gas * steps
    assert wire["calls"] == plan.collectives_per_reduction * gas * steps
    logical = d["grad_wire.reduce_logical"]
    assert logical["bytes"] == \
        plan.wire_bytes_logical_per_reduction * gas * steps


def test_overlap_counters_render_in_report(tmp_path):
    """exposed_ms/prefetch_hits flow counters -> per-step monitor
    events -> run report section (the PR-2 durable-artifact rule)."""
    from deepspeed_tpu.monitor.report import load_run, render_markdown

    eng = _make(comm=dict(BASE_COMM, overlap="auto"),
                monitor={"enabled": True, "output_path": str(tmp_path),
                         "job_name": "ovl", "flush_interval": 1})
    _train(eng, "micro", 1, steps=3)
    run = load_run(os.path.join(str(tmp_path), "ovl"))
    md = render_markdown(run)
    assert "Gradient wire levels" in md
    assert "exposed (non-overlapped) wire time" in md
    assert "`grad_wire.exposed_ms`" not in md  # not a comm byte row


# ---------------------------------------------------------------------------
# qwZ prefetch (stage 3)
# ---------------------------------------------------------------------------

def _qwz_batches(n, bs=32, dim=64, seed=3):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        yield (rng.randn(bs, dim).astype(np.float32),
               rng.randn(bs, 4).astype(np.float32))


def _make_qwz(overlap, gas=1):
    cfg = {
        "train_batch_size": 32 * gas,
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3, "quantized_weights": "int8"},
        "mesh": {"data": 8},
        "steps_per_print": 0,
        "comm": {"overlap": overlap},
    }
    engine, *_ = ds.initialize(model=SimpleModel(hidden_dim=64),
                               config_params=cfg)
    return engine


def _prefetch_landed(engine, timeout_s=30.0):
    """A `qwz.prefetch_hits` event is "the prefetch was ready when the
    forward asked": a race against the exchange thread by definition.
    Tests that pin its count wait here first, so that a loaded host
    cannot turn a hit into an on-time wait."""
    pre = engine._qwz_prefetch
    deadline = time.monotonic() + timeout_s
    while pre is not None and not pre[1].ready:
        assert time.monotonic() < deadline, "qwZ prefetch never landed"
        time.sleep(0.001)


def _train_qwz(engine, mode, gas, steps=4):
    it = _qwz_batches(steps * gas)
    loss = None
    if mode == "scan":
        for _ in range(steps):
            _prefetch_landed(engine)
            loss = engine.train_batch(it)
    else:
        for _ in range(steps * gas):
            _prefetch_landed(engine)
            loss = engine.forward(next(it))
            engine.backward()
            engine.step()
    out = (float(loss), [np.asarray(x) for x in
                         jax.tree_util.tree_leaves(engine.params)])
    engine.finalize_monitoring()
    return out


@pytest.mark.parametrize("mode,gas", [("fused", 1), ("scan", 2),
                                      ("micro", 2)])
def test_qwz_prefetch_bitwise_parity_and_hits(mode, gas):
    serial = _train_qwz(_make_qwz("none", gas=gas), mode, gas)
    snap = COUNTERS.snapshot()
    eng = _make_qwz("auto", gas=gas)
    assert eng._overlap_mode == "qwz" and eng._qwz_overlap is not None
    overlapped = _train_qwz(eng, mode, gas)
    d = COUNTERS.delta_since(snap)
    _assert_bitwise(serial, overlapped, ctx=(mode, gas))
    # steps 2..4 consume a prefetch kicked by the previous apply
    assert d["qwz.prefetch_hits"]["calls"] == 3, d["qwz.prefetch_hits"]
    # 4 consumed gathers + the final (unconsumed) prefetch kick
    assert d["qwz.gather"]["calls"] == 5 * \
        eng._qwz_gather.collectives_per_gather


def test_qwz_stale_prefetch_discarded_on_param_swap():
    eng = _make_qwz("auto", gas=1)
    it = _qwz_batches(4)
    eng.forward(next(it)); eng.backward(); eng.step()
    assert eng._qwz_prefetch is not None
    # out-of-band param replacement (load_checkpoint shape): the pending
    # prefetch no longer matches and must NOT be consumed
    eng._params = jax.tree_util.tree_map(lambda x: x + 0.0, eng._params)
    snap = COUNTERS.snapshot()
    eng.forward(next(it)); eng.backward(); eng.step()
    d = COUNTERS.delta_since(snap)
    assert "qwz.prefetch_hits" not in d, d.get("qwz.prefetch_hits")
    eng.finalize_monitoring()


# ---------------------------------------------------------------------------
# chaos hooks fire once per step on every composition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comp,comm,gas,mode", [
    ("fused", None, 1, "fused"),
    ("scan", None, 2, "scan"),
    ("split", None, 2, "micro"),
    ("overlap", dict(BASE_COMM, overlap="auto"), 2, "micro"),
])
def test_step_boundary_and_watchdog_once_per_step(monkeypatch, comp,
                                                  comm, gas, mode):
    steps = 3
    eng = _make(comm=comm, gas=gas,
                faults={"watchdog": {"enabled": True,
                                     "deadline_s": 600.0}})
    if comp == "overlap":
        assert "grads" in eng._step_fns
    boundaries = []
    real_boundary = resilience.step_boundary
    monkeypatch.setattr(resilience, "step_boundary",
                        lambda step: (boundaries.append(step),
                                      real_boundary(step))[1])
    beats = []
    real_beat = eng._watchdog.beat
    eng._watchdog.beat = lambda step: (beats.append(step),
                                       real_beat(step))[1]
    _train(eng, mode, gas, steps=steps)
    assert len(boundaries) == steps, (comp, boundaries)
    assert len(beats) == steps, (comp, beats)


# ---------------------------------------------------------------------------
# engine teardown: no thread leaks
# ---------------------------------------------------------------------------

def test_failure_path_close_logs_wedged_thread_by_name(ds_log,
                                                       monkeypatch):
    """The failure-path close: a service thread wedged past the join
    budget (here the sender worker, blocked inside a device
    materialization that never completes) must be LOGGED by name —
    `t.join(timeout)` discarding a straggler silently would leak its
    socket/buffer until process exit with no trace."""
    import time as _time

    from deepspeed_tpu.runtime.comm import overlap as ovl

    monkeypatch.setattr(ovl, "_CLOSE_JOIN_S", 0.2)
    ex = LocalExchange(world=1)
    gate = threading.Event()

    def blocked_getter():
        gate.wait(30)
        return np.zeros(1, np.uint8)

    ex.submit([(0, blocked_getter)])
    _time.sleep(0.05)  # let the worker enter the wedged getter
    try:
        ex.close()
        assert any("still alive" in r.getMessage()
                   and "dstpu-overlap-send" in r.getMessage()
                   and r.levelno >= logging.WARNING
                   for r in ds_log.records), \
            [r.getMessage() for r in ds_log.records]
    finally:
        gate.set()  # release the thread so the suite stays leak-free


def test_overlap_teardown_leaves_no_threads():
    before = {th for th in threading.enumerate() if th.is_alive()}
    eng = _make(comm=dict(BASE_COMM, overlap="auto"))
    _train(eng, "fused", 1, steps=2)  # finalize_monitoring inside
    leaked = [th for th in threading.enumerate()
              if th.is_alive() and th not in before
              and th.name.startswith("dstpu-overlap")]
    assert not leaked, leaked


# ---------------------------------------------------------------------------
# bench dry-run (tier-1 anti-rot for the --overlap lanes)
# ---------------------------------------------------------------------------

def test_grad_wire_bench_overlap_dry_run(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import grad_wire_bench as bench

    result = bench.run_dry_overlap(str(tmp_path), steps=2)
    assert result["metric"] == "grad_wire_cpu_mesh_overlap_dryrun"
    for lane in ("flat_bf16_overlap", "hier_int8_overlap"):
        entry = result[lane]
        assert entry["loss_bitwise_vs_serial"] is True
        assert "exposed_ms_per_step" in entry
        assert "exposed_wire_frac" in entry
    # the artifact landed through monitor/artifacts.py
    assert (tmp_path / "manifest.jsonl").exists()
    assert list(tmp_path.glob("*_grad_wire_cpu_mesh_overlap_dryrun.json"))
