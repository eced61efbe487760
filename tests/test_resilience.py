"""Chaos-ready runtime (runtime/resilience.py): deterministic fault
injection, the transient-vs-fatal retry taxonomy, prefetch-worker
respawn, generation-scoped hostwire gathers, the StepWatchdog hang
detector + supervisor escalation, the restart ledger, and the
chaos_bench tier-1 dry-run.

The determinism tests are the load-bearing ones: a chaos failure is
only debuggable if re-running the same FaultPlan seed + schedule
injects the identical fault sequence."""

import importlib
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.elasticity.supervisor import (HeartbeatWatcher,
                                                 RestartPolicy, supervise)
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.runtime import checkpointing as ckpt_io
from deepspeed_tpu.runtime import resilience as rz
from deepspeed_tpu.runtime.comm.hostwire import HostWire, KVSignals
from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                              PrefetchLoader)
from simple_model import SimpleModel, random_batches
from test_hostwire import FakeCoordClient


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """No test may leak an installed plan/policy into the next."""
    yield
    rz.install_fault_plan(None)
    rz.install_retry_policy(None)


def _fast_retries():
    rz.install_retry_policy(rz.RetryPolicy(max_attempts=4,
                                           base_delay_ms=1.0,
                                           max_delay_ms=4.0, jitter=0.0))


def _install(rules, seed=0):
    plan = rz.FaultPlan.from_config(rules, seed=seed)
    rz.install_fault_plan(plan)
    return plan


# ---------------------------------------------------------------------------
# taxonomy + retry
# ---------------------------------------------------------------------------


def test_transient_taxonomy():
    assert rz.is_transient(rz.InjectedFault("x"))
    assert rz.is_transient(TimeoutError("t"))
    assert rz.is_transient(ConnectionResetError("r"))
    assert rz.is_transient(RuntimeError("DEADLINE_EXCEEDED: kv get"))
    assert rz.is_transient(RuntimeError("server UNAVAILABLE"))
    assert rz.is_transient(OSError(__import__("errno").EIO, "io error"))
    # fatal: retrying cannot help / must not mask bugs
    assert not rz.is_transient(rz.InjectedFatalFault("x"))
    assert not rz.is_transient(FileNotFoundError("gone"))
    assert not rz.is_transient(PermissionError("no"))
    assert not rz.is_transient(ValueError("bad config"))
    assert not rz.is_transient(OSError(__import__("errno").ENOSPC, "full"))
    # the blocking-wait variant keeps timeouts fatal
    assert not rz.is_transient_not_timeout(TimeoutError("t"))
    assert not rz.is_transient_not_timeout(
        RuntimeError("Deadline Exceeded"))
    assert rz.is_transient_not_timeout(RuntimeError("UNAVAILABLE"))


def test_retry_transient_recovers_and_counts():
    _fast_retries()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise rz.TransientFault("blip")
        return "ok"

    snap = COUNTERS.snapshot()
    assert rz.retry_transient(flaky, site="t") == "ok"
    d = COUNTERS.delta_since(snap)
    assert d["fault.retried"]["calls"] == 2
    assert d["fault.recovered_ms"]["calls"] == 1
    assert d["fault.recovered_ms"]["bytes"] > 0


def test_retry_transient_fatal_propagates_immediately():
    _fast_retries()
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise ValueError("config bug")

    snap = COUNTERS.snapshot()
    with pytest.raises(ValueError):
        rz.retry_transient(fatal, site="t")
    assert calls["n"] == 1  # no retry burned on a fatal fault
    assert not COUNTERS.delta_since(snap).get("fault.retried")


def test_retry_transient_budget_exhaustion_reraises():
    _fast_retries()

    def always():
        raise rz.TransientFault("down hard")

    snap = COUNTERS.snapshot()
    with pytest.raises(rz.TransientFault):
        rz.retry_transient(always, site="t")
    d = COUNTERS.delta_since(snap)
    assert d["fault.retried"]["calls"] == 3  # max_attempts=4 -> 3 retries
    assert not d.get("fault.recovered_ms")


def test_retry_policy_validation():
    with pytest.raises(ValueError, match="max_attempts"):
        rz.RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="jitter"):
        rz.RetryPolicy(jitter=1.5)


# ---------------------------------------------------------------------------
# FaultPlan: schedules, kinds, determinism (tier-1 acceptance)
# ---------------------------------------------------------------------------


def test_fault_rule_validation():
    with pytest.raises(ValueError, match="kind"):
        rz.FaultRule("s", "explode")
    with pytest.raises(ValueError, match="site"):
        rz.FaultRule("", "raise")
    with pytest.raises(ValueError, match="prob"):
        rz.FaultRule("s", "raise", prob=1.5)
    with pytest.raises(ValueError, match="unknown key"):
        rz.FaultRule.from_dict({"site": "s", "kind": "raise",
                                "typo_knob": 1})
    with pytest.raises(ValueError, match="'site' and 'kind'"):
        rz.FaultRule.from_dict({"site": "s"})
    # the config-time contract: malformed schedules / negative sleeps
    # must fail HERE, never mid-training-step
    with pytest.raises(ValueError, match="delay_ms"):
        rz.FaultRule("s", "delay_ms", delay_ms=-5)
    with pytest.raises(ValueError, match="hang_s"):
        rz.FaultRule("s", "hang", hang_s=-1)
    with pytest.raises(ValueError, match="steps"):
        rz.FaultRule("s", "raise", steps=5)
    with pytest.raises(ValueError, match="calls"):
        rz.FaultRule("s", "raise", calls="0")
    with pytest.raises(ValueError, match="times"):
        rz.FaultRule("s", "raise", times=-1)


def test_fault_plan_schedules():
    plan = _install([
        {"site": "a", "kind": "raise", "calls": [1]},
        {"site": "b", "kind": "raise", "steps": [2], "times": 1},
        {"site": "c.*", "kind": "raise", "every": 2, "times": 2},
    ])
    # calls schedule: only the 2nd invocation of `a`
    rz.fault_point("a")
    with pytest.raises(rz.InjectedFault):
        rz.fault_point("a")
    rz.fault_point("a")
    # step schedule: only at step 2, once
    rz.fault_point("b")
    plan.set_step(2)
    with pytest.raises(rz.InjectedFault):
        rz.fault_point("b")
    rz.fault_point("b")  # times=1 exhausted
    # every + fnmatch: invocations 0 and 2 of c.x
    with pytest.raises(rz.InjectedFault):
        rz.fault_point("c.x")
    rz.fault_point("c.x")
    with pytest.raises(rz.InjectedFault):
        rz.fault_point("c.x")
    rz.fault_point("c.x")  # idx 3
    rz.fault_point("c.x")  # idx 4: times=2 exhausted
    assert len(plan.injection_log) == 4


def test_fault_plan_rank_scoping():
    plan = _install([{"site": "s", "kind": "raise", "rank": 1}])
    plan.rank = 0
    rz.fault_point("s")  # not our rank
    plan.rank = 1
    with pytest.raises(rz.InjectedFault):
        rz.fault_point("s")


def test_fault_kinds_delay_and_corrupt_and_fatal():
    _install([
        {"site": "d", "kind": "delay_ms", "delay_ms": 30, "times": 1},
        {"site": "p", "kind": "corrupt", "truncate_to": 3, "times": 1},
        {"site": "f", "kind": "raise", "transient": False, "times": 1},
    ])
    t0 = time.perf_counter()
    rz.fault_point("d")
    assert time.perf_counter() - t0 >= 0.025
    assert rz.fault_filter("p", b"0123456789") == b"012"
    assert rz.fault_filter("p", b"0123456789") == b"0123456789"
    with pytest.raises(rz.InjectedFatalFault):
        rz.fault_point("f")


def test_fault_plan_determinism_same_seed_identical_sequence():
    """Tier-1 acceptance: the same seed + schedule against the same
    invocation sequence injects the IDENTICAL fault sequence."""
    rules = [
        {"site": "a.*", "kind": "delay_ms", "delay_ms": 0, "prob": 0.5},
        {"site": "b", "kind": "raise", "every": 3},
    ]

    def drive(plan):
        rz.install_fault_plan(plan)
        for step in range(6):
            plan.set_step(step)
            for _ in range(4):
                rz.fault_point("a.x")
            try:
                rz.fault_point("b")
            except rz.InjectedFault:
                pass
        rz.install_fault_plan(None)
        return [(e["site"], e["kind"], e["step"], e["call"])
                for e in plan.injection_log]

    log1 = drive(rz.FaultPlan.from_config(rules, seed=7))
    log2 = drive(rz.FaultPlan.from_config(rules, seed=7))
    assert log1, "schedule injected nothing — the test is vacuous"
    assert log1 == log2
    log3 = drive(rz.FaultPlan.from_config(rules, seed=8))
    assert log3 != log1, "different seeds produced the same sequence"


def _make_engine(faults=None, monitor_path=None, job_name="rz_run",
                 watchdog=None):
    cfg = {
        "train_batch_size": 32,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 0,
    }
    fd = {}
    if faults is not None:
        fd["rules"] = faults
        fd["seed"] = 3
    if watchdog is not None:
        fd["watchdog"] = watchdog
    if fd:
        cfg["faults"] = fd
    if monitor_path is not None:
        cfg["monitor"] = {"enabled": True, "output_path": monitor_path,
                          "job_name": job_name, "flush_interval": 1,
                          "flops": False}
    engine, *_ = ds.initialize(model=SimpleModel(), config_params=cfg)
    return engine


def test_engine_fault_schedule_is_reproducible():
    """Same config, same training drive -> identical injection log
    (this is what makes an engine-level chaos failure replayable)."""
    rules = [{"site": "engine.step", "kind": "delay_ms", "delay_ms": 0,
              "prob": 0.5}]
    logs = []
    for _ in range(2):
        engine = _make_engine(faults=rules)
        it = random_batches(1000, batch_size=32, seed=7)
        for _ in range(8):
            engine.train_batch(it)
        plan = rz.active_plan()
        assert plan is not None
        logs.append([(e["site"], e["step"], e["call"])
                     for e in plan.injection_log])
    assert logs[0] == logs[1]
    assert logs[0], "prob=0.5 over 8 steps injected nothing (seed drift?)"


def test_engine_without_faults_clears_stale_plan():
    _install([{"site": "engine.step", "kind": "raise"}])
    _make_engine()  # no faults block -> installs None
    assert rz.active_plan() is None


def test_faults_config_validation():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    with pytest.raises(ValueError, match="unknown key"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "faults": {"ruels": []}}, world_size=8)
    with pytest.raises(ValueError, match="kind"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "faults": {"rules": [{"site": "s",
                                               "kind": "nope"}]}},
                        world_size=8)
    with pytest.raises(ValueError, match="max_attempts"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "faults": {"retry": {"max_attempts": 0}}},
                        world_size=8)
    with pytest.raises(ValueError, match="deadline_s"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "faults": {"watchdog": {"enabled": True,
                                                 "deadline_s": 0}}},
                        world_size=8)
    # hardening knobs parse without any rules
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "faults": {"retry": {"max_attempts": 2}}},
                          world_size=8)
    assert not cfg.faults_config.enabled
    assert cfg.faults_config.retry_policy.max_attempts == 2


# ---------------------------------------------------------------------------
# hostwire: KV retry + generation-scoped gather keys (satellite)
# ---------------------------------------------------------------------------


class StrictFakeCoordClient(FakeCoordClient):
    """The REAL coordination service refuses duplicate key_value_set
    with ALREADY_EXISTS (FakeCoordClient silently overwrites) — the
    exact behaviour that stranded un-generation-scoped retried gathers
    on a dead attempt's keys."""

    def key_value_set(self, key, value):
        with self._cv:
            if key in self._kv:
                raise RuntimeError(f"ALREADY_EXISTS: duplicate key {key}")
            self._kv[key] = str(value)
            self._cv.notify_all()


def test_kv_get_retries_injected_transient():
    _fast_retries()
    _install([{"site": "hostwire.kv_get", "kind": "raise", "calls": [0],
               "times": 1}])
    client = FakeCoordClient(1)
    client.key_value_set("k", "djE=")  # base64("v1")
    from deepspeed_tpu.runtime.comm.hostwire import _kv_get

    snap = COUNTERS.snapshot()
    assert _kv_get(client, "k", 2000) == b"v1"
    assert COUNTERS.delta_since(snap)["fault.retried"]["calls"] == 1


def test_kv_set_first_attempt_already_exists_stays_loud():
    """ALREADY_EXISTS is only 'my retry landed' when it IS a retry: on
    the first attempt it means a FOREIGN writer holds the write-once
    key (mis-ranked launch, seq bug) — swallowing it would serve peers
    someone else's bytes."""
    _fast_retries()
    from deepspeed_tpu.runtime.comm.hostwire import _kv_set

    client = StrictFakeCoordClient(1)
    client.key_value_set("k", "foreign")
    with pytest.raises(RuntimeError, match="ALREADY_EXISTS"):
        _kv_set(client, "k", b"mine")
    # but a RETRY whose first attempt landed before the ack was lost
    # resolves to success: the set stores the value THEN loses the ack
    # (transient), the retry hits ALREADY_EXISTS on its OWN key
    class LandsThenLosesAck(StrictFakeCoordClient):
        def __init__(self, world):
            super().__init__(world)
            self.first = True

        def key_value_set(self, key, value):
            super().key_value_set(key, value)  # the value IS durably up
            if self.first:
                self.first = False
                raise ConnectionResetError("ack lost")

    c2 = LandsThenLosesAck(1)
    _kv_set(c2, "k2", b"v")  # attempt 1 lands+raises; retry resolves
    import base64

    assert base64.b64decode(c2.blocking_key_value_get("k2", 100)) == b"v"


def test_kv_signals_post_retries_and_wait_timeout_does_not():
    _fast_retries()
    _install([{"site": "kv.post", "kind": "raise", "calls": [0],
               "times": 1}])
    sig = KVSignals(_endpoint=(FakeCoordClient(1), 0, 1))
    snap = COUNTERS.snapshot()
    sig.post("done/0")
    assert COUNTERS.delta_since(snap)["fault.retried"]["calls"] == 1
    assert sig.wait("done/0", timeout_ms=500) == "1"
    # a wait on a key nobody posts times out ONCE — no retry multiplier
    # on the commit barrier's dead-peer detector
    snap = COUNTERS.snapshot()
    t0 = time.perf_counter()
    with pytest.raises(Exception):
        sig.wait("never", timeout_ms=300)
    assert time.perf_counter() - t0 < 0.9  # ~1x the timeout, not 4x
    assert not COUNTERS.delta_since(snap).get("fault.retried")


def test_retried_gather_never_consumes_dead_attempts_payload():
    """Satellite regression: attempt 1 dies between `read` and `clean`
    (rank 1 keels over after posting; rank 0 times out at the read
    barrier), stranding write-once keys.  The RETRIED gather must ride
    a fresh generation: new payloads in, new payloads out — never the
    dead attempt's, and no ALREADY_EXISTS strand on the stale keys."""
    client = StrictFakeCoordClient(2)

    class DiesBeforeReadBarrier:
        """Client proxy for rank 1's first attempt: the process 'dies'
        (raises) after its payload is posted, before the read barrier."""

        def __init__(self, inner):
            self.inner = inner
            self.died = False

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def wait_at_barrier(self, name, timeout_ms):
            if not self.died:
                self.died = True
                raise RuntimeError(
                    "UNAVAILABLE: simulated death before read barrier")
            return self.inner.wait_at_barrier(name, timeout_ms)

    wires = [HostWire(tag="gen", timeout_ms=700,
                      _endpoint=(client, 0, 2)),
             HostWire(tag="gen", timeout_ms=700,
                      _endpoint=(DiesBeforeReadBarrier(client), 1, 2))]
    errs = [None, None]

    def attempt(rank, payload, out):
        try:
            out[rank] = wires[rank].allgather_bytes(payload)
        except BaseException as e:  # noqa: BLE001
            errs[rank] = e

    # attempt 1: both ranks fail (rank 1 raises; rank 0 breaks at the
    # barrier rank 1 never reaches) and the stale payloads stay behind
    res1 = [None, None]
    ts = [threading.Thread(target=attempt, args=(r, b"STALE%d" % r, res1))
          for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert errs[0] is not None and errs[1] is not None, (errs, res1)
    assert all(w._gen == 1 for w in wires), [w._gen for w in wires]
    stale_keys = [k for k in client._kv if k.startswith("gen/")]
    assert stale_keys, "the dead attempt should have stranded keys"

    # attempt 2 (the collective retry): fresh payloads round-trip
    errs[:] = [None, None]
    res2 = [None, None]
    ts = [threading.Thread(target=attempt, args=(r, b"FRESH%d" % r, res2))
          for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert errs == [None, None], errs
    assert res2[0] == res2[1] == [b"FRESH0", b"FRESH1"]


# ---------------------------------------------------------------------------
# checkpoint IO hardening (+ skip-back satellite)
# ---------------------------------------------------------------------------


def test_atomic_write_retries_transient_and_leaves_no_tmp(tmp_path):
    _fast_retries()
    _install([{"site": "ckpt.atomic_write", "kind": "raise",
               "calls": [0, 1], "times": 2}])
    path = str(tmp_path / "blob")
    snap = COUNTERS.snapshot()
    assert ckpt_io._atomic_write(path, b"hello") == 5
    with open(path, "rb") as f:
        assert f.read() == b"hello"
    assert not list(tmp_path.glob("*.tmp.*"))
    d = COUNTERS.delta_since(snap)
    assert d["fault.retried"]["calls"] == 2
    assert d["fault.injected"]["calls"] == 2
    assert d["fault.recovered_ms"]["calls"] == 1


def test_atomic_write_budget_exhaustion_raises(tmp_path):
    _fast_retries()
    _install([{"site": "ckpt.atomic_write", "kind": "raise"}])
    with pytest.raises(rz.InjectedFault):
        ckpt_io._atomic_write(str(tmp_path / "f"), b"x")
    assert not (tmp_path / "f").exists()


def test_corrupt_rule_produces_detectably_broken_checkpoint(tmp_path):
    _install([{"site": "ckpt.atomic_write.payload", "kind": "corrupt",
               "calls": [0], "times": 1, "truncate_to": 4}])
    ckpt_io.save_checkpoint_state(str(tmp_path), "t",
                                  {"module": {"w": np.arange(8.0)}})
    rz.install_fault_plan(None)
    # the torn payload must not deserialize into silent garbage
    with pytest.raises(Exception):
        ckpt_io.load_checkpoint_state(str(tmp_path), "t")


def test_read_latest_tag_counts_and_skips_uncommitted(tmp_path,
                                                      monkeypatch):
    """Satellite: skip-back names every uncommitted tag it passed and
    bumps ckpt.skipped_tags — not just the one `latest` pointed at."""
    ckpt_io.save_checkpoint_state(str(tmp_path), "good",
                                  {"module": {"w": np.arange(4.0)}})
    monkeypatch.setattr(ckpt_io, "_commit", lambda *a, **k: None)
    ckpt_io.save_checkpoint_state(str(tmp_path), "dead1",
                                  {"module": {"w": np.arange(4.0)}})
    ckpt_io.save_checkpoint_state(str(tmp_path), "dead2",
                                  {"module": {"w": np.arange(4.0)}})
    monkeypatch.undo()
    with open(tmp_path / "latest", "w") as f:
        f.write("dead2")
    snap = COUNTERS.snapshot()
    assert ckpt_io.read_latest_tag(str(tmp_path)) == "good"
    d = COUNTERS.delta_since(snap)
    assert d["ckpt.skipped_tags"]["calls"] == 2
    assert ckpt_io.uncommitted_tags(str(tmp_path)) == ["dead1", "dead2"]


def test_same_tag_commits_to_different_dirs_use_distinct_keys(tmp_path):
    """Found by the chaos campaign against the REAL coordination
    service: the commit barrier's KV keys were scoped (tag, seq) only,
    so same-tag saves into two different directories collided on one
    write-once committed-key (ALREADY_EXISTS on the second commit).
    Keys are now additionally scoped by a save_dir hash."""
    W = 2
    client = StrictFakeCoordClient(W)
    for d in ("dirA", "dirB"):
        os.makedirs(tmp_path / d / "tag", exist_ok=True)
        errs = []

        def run(rank, d=d):
            try:
                ckpt_io._commit(str(tmp_path / d), "tag", None, False, 0,
                                commit_endpoint=(client, rank, W), seq=0)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append((rank, e))

        ts = [threading.Thread(target=run, args=(r,)) for r in range(W)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not errs, (d, errs)
        assert ckpt_io.is_tag_committed(str(tmp_path / d), "tag")
    # and the two directories really used distinct key namespaces
    committed = [k for k in client._kv if k.endswith("/committed")]
    assert len(committed) == 2, committed


# ---------------------------------------------------------------------------
# prefetch-worker respawn
# ---------------------------------------------------------------------------


def _toy_loader(n_batches=6, batch=8):
    data = [(np.full((4,), i, np.float32),
             np.full((2,), -i, np.float32))
            for i in range(n_batches * batch)]
    return DeepSpeedDataLoader(data, batch_size=batch,
                               data_parallel_world_size=1,
                               data_parallel_rank=0)


def test_worker_death_respawns_with_identical_batches():
    loader = _toy_loader()
    expect = [jax.tree_util.tree_map(np.asarray, b) for b in loader]
    _install([{"site": "dataloader.worker", "kind": "raise",
               "calls": [2], "times": 1}])
    pl = PrefetchLoader(_toy_loader(), prefetch_depth=2, num_workers=2,
                        respawn_backoff_s=0.01)
    snap = COUNTERS.snapshot()
    got = list(iter(pl))
    assert len(got) == len(expect)
    for a, b in zip(got, expect):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(x, y)
    d = COUNTERS.delta_since(snap)
    assert d["input.worker_respawns"]["calls"] == 1
    assert d["fault.injected"]["calls"] == 1


def test_worker_death_budget_exhaustion_reraises():
    _install([{"site": "dataloader.worker", "kind": "raise"}])
    pl = PrefetchLoader(_toy_loader(), prefetch_depth=2, num_workers=1,
                        max_respawns=2, respawn_backoff_s=0.01)
    snap = COUNTERS.snapshot()
    with pytest.raises(rz.InjectedFault):
        list(iter(pl))
    assert COUNTERS.delta_since(snap)["input.worker_respawns"][
        "calls"] == 2
    pl.close()


# ---------------------------------------------------------------------------
# watchdog: trip -> snapshot -> supervisor escalation
# ---------------------------------------------------------------------------


def test_watchdog_trips_snapshots_and_rearms(tmp_path):
    run_dir = str(tmp_path / "run")
    trips = []
    wd = rz.StepWatchdog(0.15, run_dir, poll_s=0.02, rank=3,
                         on_trip=trips.append)
    try:
        snap = COUNTERS.snapshot()
        wd.beat(7)
        deadline = time.monotonic() + 5
        while wd.trips < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert wd.trips == 1
        time.sleep(0.1)  # one trip per stall: no re-trip without a beat
        assert wd.trips == 1
        assert COUNTERS.delta_since(snap)["watchdog.trips"]["calls"] == 1
        # the callback runs after the snapshot is written: on a loaded
        # host that is later than the counter (wait, do not assume)
        while not trips and time.monotonic() < deadline:
            time.sleep(0.02)
        assert trips and trips[0]["last_step"] == 7
        trip = rz.read_watchdog_trip(run_dir)
        assert trip is not None and "after step 7" in trip["reason"]
        assert os.path.isfile(trip["snapshot"])
        with open(trip["snapshot"]) as f:
            snapshot = json.load(f)
        # the diagnostic core: WHAT was the process blocked on
        assert any("MainThread" in k for k in snapshot["stacks"])
        assert snapshot["counters"] and snapshot["rank"] == 3
        # a fresh beat re-arms: the next stall trips again
        wd.beat(8)
        deadline = time.monotonic() + 5
        while wd.trips < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert wd.trips == 2
    finally:
        wd.stop()


def test_heartbeat_watcher_escalates_on_watchdog_trip(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    watcher = HeartbeatWatcher(run_dir, stall_timeout=0.0)
    assert watcher.check() is None
    time.sleep(0.05)
    wd = rz.StepWatchdog(0.1, run_dir, poll_s=0.02, rank=1)
    try:
        wd.beat(4)
        deadline = time.monotonic() + 5
        while wd.trips < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        wd.stop()
    trigger = watcher.check()
    assert trigger is not None
    assert "watchdog trip on rank 1" in trigger["reason"]
    assert trigger["diagnostics"] and \
        os.path.isfile(trigger["diagnostics"])
    # reset() re-arms: the SAME trip must not re-trigger the relaunched
    # child (the restart it caused already happened)
    watcher.reset()
    assert watcher.check() is None


def test_engine_watchdog_trips_on_injected_hang(tmp_path):
    """End to end: a `hang` injection at the step boundary trips the
    engine-armed watchdog, which snapshots + escalates into the monitor
    run dir (acceptance criterion)."""
    run_root = str(tmp_path / "runs")
    watcher = HeartbeatWatcher(os.path.join(run_root, "wd_run"),
                               stall_timeout=0.0)
    # deadline must clear legitimate slow steps (first-step compile on
    # the 1-core box) so only the injected hang trips it
    engine = _make_engine(
        faults=[{"site": "engine.step", "kind": "hang", "hang_s": 4.0,
                 "steps": [1]}],
        monitor_path=run_root, job_name="wd_run",
        watchdog={"enabled": True, "deadline_s": 1.8, "poll_s": 0.05})
    it = random_batches(1000, batch_size=32, seed=7)
    snap = COUNTERS.snapshot()
    for _ in range(3):
        engine.train_batch(it)
    engine.finalize_monitoring()
    assert COUNTERS.delta_since(snap)["watchdog.trips"]["calls"] == 1
    trigger = watcher.check()
    assert trigger is not None and "watchdog trip" in trigger["reason"]
    assert trigger["diagnostics"] and os.path.isfile(
        trigger["diagnostics"])


# ---------------------------------------------------------------------------
# supervisor restart ledger (satellite)
# ---------------------------------------------------------------------------


def test_supervisor_appends_restart_ledger(tmp_path):
    ledger = str(tmp_path / "restarts.jsonl")
    policy = RestartPolicy(max_restarts=1, backoff=0.01, jitter=0.0,
                           success_window=1e9)
    rc = supervise([sys.executable, "-c", "import sys; sys.exit(5)"],
                   policy=policy, ledger_path=ledger)
    assert rc == 5
    with open(ledger) as f:
        entries = [json.loads(line) for line in f if line.strip()]
    assert [e["event"] for e in entries] == ["restart", "give_up"]
    assert entries[0]["exit_code"] == 5
    assert entries[0]["reason"] == "exit code 5"
    assert entries[0]["backoff_s"] is not None
    assert entries[1]["backoff_s"] is None
    assert entries[1]["attempt"] == 2


def test_supervisor_ledger_defaults_into_monitor_dir(tmp_path):
    mon = str(tmp_path / "mon")
    os.makedirs(mon)
    policy = RestartPolicy(max_restarts=0, backoff=0.01, jitter=0.0,
                           success_window=1e9)
    supervise([sys.executable, "-c", "import sys; sys.exit(3)"],
              policy=policy, monitor_dir=mon, stall_timeout=0.0)
    path = os.path.join(mon, "restarts.jsonl")
    assert os.path.isfile(path)
    with open(path) as f:
        entries = [json.loads(line) for line in f if line.strip()]
    assert entries[-1]["event"] == "give_up"


# ---------------------------------------------------------------------------
# counters -> run report
# ---------------------------------------------------------------------------


def test_fault_counters_flow_into_run_report(tmp_path):
    from deepspeed_tpu.monitor.report import load_run, render_markdown

    engine = _make_engine(
        faults=[{"site": "ckpt.atomic_write", "kind": "raise",
                 "calls": [0], "times": 1}],
        monitor_path=str(tmp_path / "runs"), job_name="rz_report")
    _fast_retries()
    it = random_batches(1000, batch_size=32, seed=7)
    engine.train_batch(it)
    engine.save_checkpoint(str(tmp_path / "ck"))
    engine.train_batch(it)  # the step event carries the deltas
    engine.finalize_monitoring()
    run = load_run(str(tmp_path / "runs" / "rz_report"))
    md = render_markdown(run)
    assert "## Resilience" in md
    assert "faults injected" in md and "transient retries" in md
    # fault.* stays out of the comm counter table
    assert "`fault.injected`" not in md and "`fault.retried`" not in md


# ---------------------------------------------------------------------------
# chaos_bench: tier-1 CPU dry-run + slow 2-proc campaign
# ---------------------------------------------------------------------------


def _import_tool(name):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


def test_chaos_bench_dry_run(tmp_path):
    """Tier-1 cover for tools/chaos_bench.py: the CPU campaign asserts
    loss parity + pinned counters + the watchdog lane internally; here
    we pin the recorded artifact shape (the PR-2 durable-artifact
    rule)."""
    bench = _import_tool("chaos_bench")
    result = bench.run_dry(artifact_root=str(tmp_path / "runs"), steps=4,
                           record=True, root=str(tmp_path / "scratch"))
    assert result["faults_injected"] == len(bench.DRY_CHAOS_RULES) == 3
    assert result["transient_retries"] == 1
    assert result["worker_respawns"] == 1
    assert result["watchdog_trips"] == 1
    assert result["loss_parity"] == "exact"
    assert result["supervisor_restarts"] == 0
    assert os.path.isfile(tmp_path / "runs" /
                          os.path.basename(result["artifact"]))
    with open(tmp_path / "runs" / "manifest.jsonl") as f:
        assert "chaos_cpu_dryrun" in f.read()


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_campaign_2proc_tcp(tmp_path):
    """Acceptance: >=3 distinct fault kinds (transient KV raise,
    checkpoint-write raise, worker death) on the 2-proc TCP lane —
    training completes with loss parity vs the fault-free lane and zero
    supervisor restarts, counters pinned exactly."""
    bench = _import_tool("chaos_bench")
    result = bench.run_tcp(nproc=2, steps=6, record=False,
                           scratch=str(tmp_path / "scratch"))
    assert result["faults_injected"] == len(bench.tcp_chaos_rules()) == 4
    assert result["transient_retries"] >= 3
    assert result["worker_respawns"] == 1
    assert result["loss_parity"] == "exact"
    assert result["supervisor_restarts"] == 0
    assert result["ranks"][0]["losses"] == result["ranks"][1]["losses"]
