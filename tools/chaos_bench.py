#!/usr/bin/env python
"""Chaos bench: scripted fault campaigns against the training runtime.

The point of the chaos runtime (runtime/resilience.py) is a provable
claim: a run that absorbs injected faults finishes with the SAME losses
as the fault-free run, with zero supervisor restarts — transient
KV/storage/worker failures are absorbed by retry/respawn instead of
being promoted to process death.  This tool runs that claim as a bench
and records the fault/retry/recovery accounting as durable artifacts
(the PR-2 rule).

Campaigns:

* **CPU dry-run** (default; also wired into tier-1 via
  tests/test_resilience.py, like grad_wire_bench/ckpt_bench): two lanes
  on the virtual mesh —
    baseline   fault-free training + checkpointing
    chaos      identical training with a FaultPlan injecting a
               transient checkpoint-write raise, a prefetch-worker
               death, and a step delay
  asserts byte-identical loss sequences, a committed final checkpoint,
  and PINS the fault counters (fault.injected / fault.retried /
  input.worker_respawns) exactly.  A third mini-lane injects a `hang`
  at the step boundary under an armed StepWatchdog and asserts the
  trip: diagnostic snapshot + `watchdog_trip.json` escalation that the
  supervisor's HeartbeatWatcher picks up as a restart trigger.

* **--nproc 2** (TCP): the same two lanes across 2 jax.distributed
  processes, where the KV faults hit the REAL coordination-service
  transport: transient raises on the commit-barrier done-key post and
  the heartbeat-wire KV gets, plus the checkpoint-write raise and the
  worker death.  Loss parity is asserted on every rank; the recorded
  artifact carries per-rank fault/retry counters.

* **--overlap** (CPU dry-run, also tier-1 via
  tests/test_overlap_healing.py): campaigns against the self-healing
  host exchange (runtime/comm/overlap.py) — a transient exchange.send
  raise absorbed by the retry taxonomy, a sustained send fault driving
  COORDINATED DEMOTION to the serial in-program wire (bitwise losses,
  `exchange.demotions` pinned), and a SIGTERM mid-run producing a
  committed emergency checkpoint that resumes with exact loss parity.

* **--overlap --nproc 2** (TCP): the same claims over the REAL socket
  mesh — a reconnect lane injecting a connection reset (send fault), a
  peer-kill-shaped recv fault, and a CRC-caught frame corruption, all
  healed by reconnect+resend (`exchange.reconnects` pinned exactly, one
  per rank per injected drop; zero demotions, zero restarts, bitwise
  losses); a demotion lane with the reconnect budget zeroed that
  completes the run on the serial wire; and a two-phase preemption lane
  where both ranks SIGTERM mid-run, commit the emergency checkpoint
  through the real coordination-service barrier, exit cleanly, and a
  relaunched pair resumes to bitwise-identical final params.

Usage: python tools/chaos_bench.py [--nproc 2] [--steps 6]
           [--no-record] [--overlap]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

DIM = 64
BATCH = 32


class _SyntheticRegression:
    """Deterministic indexable dataset (the index protocol is what lets
    PrefetchLoader parallelize collate — and what the worker-death
    respawn path needs to replay the exact failed batch)."""

    def __init__(self, n, dim=DIM, out=4, seed=0):
        import numpy as np

        rng = np.random.RandomState(seed)
        self.x = rng.randn(n, dim).astype(np.float32)
        w = rng.randn(dim, out).astype(np.float32)
        self.y = (self.x @ w).astype(np.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return (self.x[i], self.y[i])


def _mlp(dim=DIM, out=4):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.module import TrainModule

    class MLP(TrainModule):
        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {"w1": jax.random.normal(k1, (dim, dim)) * 0.1,
                    "b1": jnp.zeros((dim,)),
                    "w2": jax.random.normal(k2, (dim, out)) * 0.1,
                    "b2": jnp.zeros((out,))}

        def loss(self, params, batch, rng=None, train=True, **kw):
            x, y = batch
            h = jnp.tanh(x @ params["w1"] + params["b1"])
            pred = h @ params["w2"] + params["b2"]
            return jnp.mean((pred - y.astype(pred.dtype)) ** 2)

    return MLP()


def run_lane(steps, ckpt_dir, faults=None, monitor_path=None,
             job_name="chaos", save_every=2, num_workers=2, batch=BATCH,
             watchdog=None):
    """One campaign lane: train `steps` global batches off the engine-
    owned prefetched loader, checkpointing every `save_every` steps.
    Returns (losses, counter_deltas, engine_done_marker)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.runtime import checkpointing as ckpt_io

    cfg = {
        "train_batch_size": batch,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "data_pipeline": {"num_workers": num_workers},
    }
    faults_cfg = {}
    if faults:
        faults_cfg["rules"] = faults
    if watchdog:
        faults_cfg["watchdog"] = watchdog
    if faults_cfg:
        cfg["faults"] = faults_cfg
    if monitor_path is not None:
        cfg["monitor"] = {"enabled": True, "output_path": monitor_path,
                          "job_name": job_name, "flush_interval": 1,
                          "flops": False, "heartbeat_interval": 1}
    dataset = _SyntheticRegression(steps * batch)
    engine, *_ = ds.initialize(model=_mlp(), config_params=cfg,
                               training_data=dataset,
                               dist_init_required=False)
    snap = COUNTERS.snapshot()
    losses = []
    for i in range(steps):
        losses.append(float(engine.train_batch()))
        if save_every and (i + 1) % save_every == 0:
            engine.save_checkpoint(ckpt_dir, tag=f"step{i + 1}")
    ckpt_io.flush_pending()
    delta = COUNTERS.delta_since(snap)
    engine.finalize_monitoring()
    committed = ckpt_io.read_latest_tag(ckpt_dir) if save_every else None
    return losses, delta, committed


# the dry-run chaos schedule: three distinct fault kinds, all absorbed
# (a raise retried, a worker death respawned, a delay ridden out) —
# tests pin the resulting counters EXACTLY against this list
DRY_CHAOS_RULES = [
    # first checkpoint file write dies once with a transient error;
    # retry_transient absorbs it (storage-hiccup model)
    {"site": "ckpt.atomic_write", "kind": "raise", "calls": [0],
     "times": 1},
    # a prefetch worker dies mid-epoch; the consumer respawns it at the
    # exact failed batch (dead-data-worker model)
    {"site": "dataloader.worker", "kind": "raise", "calls": [1],
     "times": 1},
    # one slow step (GC pause / snapshot stall model)
    {"site": "engine.step", "kind": "delay_ms", "delay_ms": 5,
     "steps": [1], "times": 1},
]


def run_dry(artifact_root=None, steps=4, record=True, root=None):
    """Tier-1 CPU campaign (in-process; the grad_wire/ckpt_bench
    dry-run pattern): baseline vs chaos lanes must produce IDENTICAL
    losses with the chaos lane's fault counters pinned, plus the
    watchdog hang lane.  Returns the recorded result dict."""
    from deepspeed_tpu.elasticity.supervisor import HeartbeatWatcher
    from deepspeed_tpu.monitor.counters import COUNTERS

    made_root = root is None
    root = root or tempfile.mkdtemp(prefix="chaos_bench_")
    try:
        base_losses, base_delta, base_tag = run_lane(
            steps, os.path.join(root, "ck_base"))
        chaos_losses, chaos_delta, chaos_tag = run_lane(
            steps, os.path.join(root, "ck_chaos"),
            faults=DRY_CHAOS_RULES)

        assert base_losses == chaos_losses, (
            f"chaos lane diverged: {base_losses} vs {chaos_losses} — "
            f"an injected fault leaked into training instead of being "
            f"absorbed")
        assert base_tag == chaos_tag == f"step{steps - steps % 2}", \
            (base_tag, chaos_tag)
        injected = chaos_delta.get("fault.injected", {}).get("calls", 0)
        retried = chaos_delta.get("fault.retried", {}).get("calls", 0)
        respawns = chaos_delta.get("input.worker_respawns",
                                   {}).get("calls", 0)
        recovered = chaos_delta.get("fault.recovered_ms", {})
        assert injected == len(DRY_CHAOS_RULES), chaos_delta
        assert retried == 1 and respawns == 1, chaos_delta
        assert recovered.get("calls", 0) == 1, chaos_delta
        assert not base_delta.get("fault.injected"), base_delta

        # watchdog lane: a hang at the step boundary must trip the
        # watchdog, dump the snapshot, and leave the supervisor
        # escalation file where HeartbeatWatcher finds it
        run_root = os.path.join(root, "runs")
        run_dir = os.path.join(run_root, "wd")
        watcher = HeartbeatWatcher(run_dir, stall_timeout=0.0)
        wd_snap = COUNTERS.snapshot()
        # deadline sizing: it must exceed the worst-case LEGITIMATE
        # inter-beat gap (first-step compile + a synchronous save's
        # fsync can reach ~1s on a loaded 1-core box) while the hang
        # clears it with margin — a spurious trip here would be the
        # bench failing its own product
        wd_losses, wd_delta, _ = run_lane(
            steps, os.path.join(root, "ck_wd"),
            faults=[{"site": "engine.step", "kind": "hang",
                     "hang_s": 4.0, "steps": [2]}],
            monitor_path=run_root, job_name="wd",
            watchdog={"enabled": True, "deadline_s": 1.8, "poll_s": 0.05})
        trips = COUNTERS.delta_since(wd_snap).get("watchdog.trips",
                                                  {}).get("calls", 0)
        assert trips == 1, f"hang did not trip the watchdog ({wd_delta})"
        assert wd_losses == base_losses, "the hang changed training"
        trip_path = os.path.join(run_dir, "watchdog_trip.json")
        assert os.path.isfile(trip_path), "no escalation file"
        with open(trip_path) as f:
            trip = json.load(f)
        assert trip["snapshot"] and os.path.isfile(trip["snapshot"]), trip
        with open(trip["snapshot"]) as f:
            snapshot = json.load(f)
        assert snapshot["stacks"] and snapshot["counters"], \
            "snapshot missing stacks/counters"
        trigger = watcher.check()
        assert trigger is not None and "watchdog trip" in \
            trigger["reason"], trigger
        assert trigger["diagnostics"] == trip["snapshot"], trigger

        result = {
            "metric": "chaos_cpu_dryrun",
            "platform": "cpu",
            "steps": steps,
            "faults_injected": injected,
            "transient_retries": retried,
            "worker_respawns": respawns,
            "recovered_ms": round(recovered.get("bytes", 0) / 1000.0, 3),
            "watchdog_trips": trips,
            "loss_parity": "exact",
            "supervisor_restarts": 0,
            "value": injected + trips,
            "unit": "faults_absorbed_or_escalated",
            "losses": [round(x, 6) for x in base_losses],
        }
        if record:
            from deepspeed_tpu.monitor.artifacts import record_bench_result

            result["artifact"] = record_bench_result(
                result, root=artifact_root, name=result["metric"])
        return result
    finally:
        # never leak the campaign's fault plan into the caller's process
        from deepspeed_tpu.runtime import resilience

        resilience.install_fault_plan(None)
        resilience.install_retry_policy(None)
        if made_root:
            shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# 2-process TCP campaign: KV faults hit the real coordination service
# ---------------------------------------------------------------------------

# rank-scoped so the two ranks inject DIFFERENT faults (the asymmetric
# case is the hard one: the other rank must ride out its peer's retry
# window inside the ordinary KV timeouts)
def tcp_chaos_rules():
    return [
        # transient KV raise on the commit barrier's done-key post
        {"site": "kv.post", "kind": "raise", "calls": [0], "times": 1,
         "rank": 0},
        # transient KV raise inside the heartbeat wire's part-key get
        {"site": "hostwire.kv_get", "kind": "raise", "calls": [1],
         "times": 1, "rank": 1},
        # checkpoint-write raise on the writing rank (at stage 0 with
        # replicated params only process 0 lands files)
        {"site": "ckpt.atomic_write", "kind": "raise", "calls": [0],
         "times": 1, "rank": 0},
        # prefetch worker death on rank 1
        {"site": "dataloader.worker", "kind": "raise", "calls": [1],
         "times": 1, "rank": 1},
    ]


def _worker(args):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=args.coord,
                               num_processes=args.nproc,
                               process_id=args.proc_id)
    import deepspeed_tpu  # noqa: F401  (gloo-collectives flag first)
    from deepspeed_tpu.monitor.counters import COUNTERS  # noqa: F401

    root = args.scratch
    base_losses, base_delta, base_tag = run_lane(
        args.steps, os.path.join(root, "ck_base"),
        monitor_path=os.path.join(root, "runs"), job_name="base",
        num_workers=2)
    chaos_losses, chaos_delta, chaos_tag = run_lane(
        args.steps, os.path.join(root, "ck_chaos"),
        faults=tcp_chaos_rules(),
        monitor_path=os.path.join(root, "runs"), job_name="chaos",
        num_workers=2)

    assert base_losses == chaos_losses, (
        f"rank {args.proc_id}: chaos lane diverged "
        f"({base_losses} vs {chaos_losses})")
    assert base_tag == chaos_tag and chaos_tag is not None, \
        (base_tag, chaos_tag)
    assert not base_delta.get("fault.injected"), base_delta
    print("CHAOS_RANK " + json.dumps({
        "rank": args.proc_id,
        "losses": [round(x, 6) for x in chaos_losses],
        "final_tag": chaos_tag,
        "faults_injected": chaos_delta.get("fault.injected",
                                           {}).get("calls", 0),
        "transient_retries": chaos_delta.get("fault.retried",
                                             {}).get("calls", 0),
        "worker_respawns": chaos_delta.get("input.worker_respawns",
                                           {}).get("calls", 0),
        "recovered_ms": round(chaos_delta.get("fault.recovered_ms",
                                              {}).get("bytes", 0)
                              / 1000.0, 3),
    }), flush=True)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_tcp(nproc=2, steps=6, record=True, scratch=None, timeout=900):
    """Launch the N-process campaign; parent collects per-rank results,
    asserts the invariants, and records the artifact."""
    made = scratch is None
    scratch = scratch or tempfile.mkdtemp(prefix="chaos_tcp_")
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # CPU lane, never the chip
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--proc-id", str(i), "--nproc", str(nproc),
             "--coord", coord, "--steps", str(steps),
             "--scratch", scratch],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for i in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            assert p.returncode == 0, out[-4000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        if made:
            shutil.rmtree(scratch, ignore_errors=True)

    ranks = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("CHAOS_RANK "):
                ranks.append(json.loads(line[len("CHAOS_RANK "):]))
    assert len(ranks) == nproc, outs
    ranks.sort(key=lambda r: r["rank"])
    # every rank saw the identical (global-mean) loss stream and agreed
    # on the final committed tag
    assert all(r["losses"] == ranks[0]["losses"] for r in ranks), ranks
    assert all(r["final_tag"] == ranks[0]["final_tag"] for r in ranks)
    total_injected = sum(r["faults_injected"] for r in ranks)
    # every rule is rank-scoped and times=1: the campaign injects
    # EXACTLY one fault per rule
    expected = len(tcp_chaos_rules())
    assert total_injected == expected, (total_injected, expected, ranks)
    assert sum(r["transient_retries"] for r in ranks) >= 3, ranks
    assert sum(r["worker_respawns"] for r in ranks) == 1, ranks

    result = {
        "metric": f"chaos_{nproc}proc_tcp",
        "platform": "cpu",
        "world": {"processes": nproc},
        "steps": steps,
        "fault_kinds": ["kv.post raise", "hostwire.kv_get raise",
                        "ckpt.atomic_write raise",
                        "dataloader.worker death"],
        "faults_injected": total_injected,
        "transient_retries": sum(r["transient_retries"] for r in ranks),
        "worker_respawns": sum(r["worker_respawns"] for r in ranks),
        "recovered_ms": round(sum(r["recovered_ms"] for r in ranks), 3),
        "loss_parity": "exact",
        "supervisor_restarts": 0,
        "value": total_injected,
        "unit": "faults_absorbed",
        "ranks": ranks,
    }
    if record:
        from deepspeed_tpu.monitor.artifacts import record_bench_result

        result["artifact"] = record_bench_result(result,
                                                 name=result["metric"])
    return result


# ---------------------------------------------------------------------------
# overlap-wire campaigns: self-healing exchange, demotion, preemption
# ---------------------------------------------------------------------------

OVERLAP_PREEMPT_AT = 4  # 0-based step that self-delivers SIGTERM


def _wait_wire_quiescent(engine, timeout=20.0):
    """Block until the exchange's resend buffer drains (every frame the
    sender retained has been ACKed by every peer).  Campaign faults
    then hit a QUIET wire, so `exchange.resends` pins tightly to the
    injection schedule instead of racing whatever ACKs were in flight.
    No-op for the in-process transport and once the KV fallback owns
    the wire (no ACKs ride the KV transport — waiting would only burn
    the timeout)."""
    ex = getattr(engine, "_overlap_exchange", None)
    unacked = getattr(ex, "_unacked", None)
    if ex is None or unacked is None:
        return
    deadline = time.monotonic() + timeout
    while unacked and not getattr(ex, "_kv_mode", False) and \
            time.monotonic() < deadline:
        time.sleep(0.005)


def overlap_lane(steps, comm=None, faults=None, preempt_dir=None,
                 sigterm_step=None, resume=None, seed=0):
    """One overlap-campaign lane: manual forward/backward/step loop
    (the split composition — step boundaries, where demotion and
    preemption land, are explicit), deterministic synthetic batches.

    `sigterm_step` self-delivers SIGTERM right before that step's
    boundary — the honest preemption shape (the signal lands mid-step;
    the handler defers to the boundary), made deterministic.  The lane
    then raises SystemExit(0) out of engine.step() after the emergency
    checkpoint commits.  `resume=(dir, tag, skip)` restores the tag and
    skips the consumed batches first.

    Returns (losses, params, counter_delta, engaged)."""
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.monitor.counters import COUNTERS

    cfg = {
        "train_batch_size": BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "comm": dict({"gradient_reduction": "bucketed",
                      "reduce_bucket_size": 2048, "overlap": "auto"},
                     **(comm or {})),
    }
    if faults:
        cfg["faults"] = {"rules": faults}
    if preempt_dir:
        cfg["checkpoint"] = {"preempt_save_dir": preempt_dir}
    data = _SyntheticRegression(steps * BATCH, seed=seed)
    engine, *_ = ds.initialize(model=_mlp(), config_params=cfg,
                               dist_init_required=False)
    engaged = "grads" in engine._step_fns
    skip = 0
    if resume is not None:
        rdir, rtag, skip = resume
        engine.load_checkpoint(rdir, tag=rtag)
    snap = COUNTERS.snapshot()
    losses = []
    for i in range(skip, steps):
        batch = (data.x[i * BATCH:(i + 1) * BATCH],
                 data.y[i * BATCH:(i + 1) * BATCH])
        loss = engine.forward(batch)
        engine.backward()
        if sigterm_step is not None and i == sigterm_step:
            os.kill(os.getpid(), signal.SIGTERM)
        engine.step()
        losses.append(float(loss))
        _wait_wire_quiescent(engine)
    delta = COUNTERS.delta_since(snap)
    params = [np.asarray(x) for x in
              jax.tree_util.tree_leaves(engine.params)]
    engine.finalize_monitoring()
    return losses, params, delta, engaged


def _params_digest(params) -> str:
    h = hashlib.sha256()
    for p in params:
        h.update(p.tobytes())
    return h.hexdigest()


def _assert_params_equal(a, b, ctx):
    import numpy as np

    for x, y in zip(a, b):
        assert (x == y).all(), \
            f"{ctx}: params diverged (max |d|={np.abs(x - y).max()})"


def run_dry_overlap(artifact_root=None, steps=6, record=True, root=None):
    """Tier-1 CPU overlap campaign (in-process LocalExchange transport,
    same driver machinery as the socket mesh).  Lanes:

      serial     overlap off — the loss/params oracle
      overlap    fault-free overlap — bitwise vs serial
      transient  one exchange.send raise, absorbed by retry_transient
                 (no demotion, bitwise, fault counters pinned)
      demote     sustained send faults exhaust the retry budget ->
                 coordinated demotion: the step programs rebuild on the
                 serial wire MID-RUN and the run completes bitwise
                 (`exchange.demotions` == 1)
      preempt    SIGTERM mid-run -> committed emergency checkpoint ->
                 clean exit -> a fresh engine resumes from the tag and
                 finishes with exact loss/param parity
    """
    made_root = root is None
    root = root or tempfile.mkdtemp(prefix="chaos_overlap_")
    try:
        serial_losses, serial_params, _, _ = overlap_lane(
            steps, comm={"overlap": "none"})
        ovl_losses, ovl_params, ovl_delta, engaged = overlap_lane(steps)
        assert engaged, "overlap did not engage on the bucketed wire"
        assert ovl_losses == serial_losses, \
            f"overlap diverged: {serial_losses} vs {ovl_losses}"
        _assert_params_equal(serial_params, ovl_params, "overlap lane")
        assert not ovl_delta.get("exchange.demotions"), ovl_delta

        tr_losses, tr_params, tr_delta, _ = overlap_lane(
            steps, faults=[{"site": "exchange.send", "kind": "raise",
                            "calls": [1], "times": 1}])
        assert tr_losses == serial_losses, "transient fault leaked"
        _assert_params_equal(serial_params, tr_params, "transient lane")
        assert tr_delta.get("fault.injected", {}).get("calls") == 1
        assert tr_delta.get("fault.retried", {}).get("calls") == 1
        assert not tr_delta.get("exchange.demotions"), \
            "a single transient send fault must NOT demote"

        demote_steps = list(range(2, steps))
        dm_losses, dm_params, dm_delta, _ = overlap_lane(
            steps, faults=[{"site": "exchange.send", "kind": "raise",
                            "steps": demote_steps}])
        assert dm_losses == serial_losses, \
            f"demotion lane diverged: {serial_losses} vs {dm_losses}"
        _assert_params_equal(serial_params, dm_params, "demotion lane")
        demotions = dm_delta.get("exchange.demotions", {}).get("calls", 0)
        assert demotions == 1, dm_delta

        # preemption: SIGTERM mid-run -> committed tag -> clean exit
        from deepspeed_tpu.runtime import checkpointing as ckpt_io

        preempt_dir = os.path.join(root, "preempt_ck")
        exited = False
        try:
            overlap_lane(steps, preempt_dir=preempt_dir,
                         sigterm_step=OVERLAP_PREEMPT_AT)
        except SystemExit as e:
            exited = e.code == 0
        assert exited, "SIGTERM did not exit cleanly after the save"
        tag = ckpt_io.read_latest_tag(preempt_dir)
        assert tag == f"preempt_step{OVERLAP_PREEMPT_AT + 1}", tag
        rs_losses, rs_params, _, _ = overlap_lane(
            steps, resume=(preempt_dir, tag, OVERLAP_PREEMPT_AT + 1))
        assert rs_losses == serial_losses[OVERLAP_PREEMPT_AT + 1:], \
            (rs_losses, serial_losses)
        _assert_params_equal(serial_params, rs_params, "preempt resume")

        result = {
            "metric": "chaos_overlap_cpu_dryrun",
            "platform": "cpu",
            "steps": steps,
            "transient_absorbed": 1,
            "demotions": demotions,
            "preempt_tag": tag,
            "loss_parity": "exact",
            "supervisor_restarts": 0,
            "value": demotions + 1,
            "unit": "exchange_faults_absorbed_or_demoted",
            "losses": [round(x, 6) for x in serial_losses],
        }
        if record:
            from deepspeed_tpu.monitor.artifacts import record_bench_result

            result["artifact"] = record_bench_result(
                result, root=artifact_root, name=result["metric"])
        return result
    finally:
        from deepspeed_tpu.runtime import resilience

        resilience.install_fault_plan(None)
        resilience.install_retry_policy(None)
        if made_root:
            shutil.rmtree(root, ignore_errors=True)


# the 2-proc reconnect schedule: three distinct wire faults, each
# healed by reconnect+resend.  Windows are two steps wide (times=1, so
# each rule still injects EXACTLY once) and non-overlapping, with the
# inter-step quiescence wait ensuring each fault hits a drained wire.
def overlap_reconnect_rules():
    return [
        # connection reset: the send-side fault tears the conn down
        # before the frame hits the wire (frame stays unacked -> resent)
        {"site": "exchange.send", "kind": "raise", "steps": [1, 2],
         "times": 1, "rank": 0},
        # peer kill as the receiver sees it: the recv loop dies
        # mid-frame and the connection is torn down
        {"site": "exchange.recv", "kind": "raise", "steps": [3, 4],
         "times": 1, "rank": 1},
        # frame corruption: the payload is truncated in flight; the CRC
        # turns it into a connection fault the resend path heals
        {"site": "exchange.payload", "kind": "corrupt", "truncate_to": 3,
         "steps": [5, 6], "times": 1, "rank": 0},
    ]


def overlap_demotion_rules():
    return [
        # one torn connection with the reconnect budget zeroed: the
        # exchange falls back to the KV transport and the ranks demote
        {"site": "exchange.recv", "kind": "raise", "steps": [2, 3],
         "times": 1, "rank": 1},
    ]


def _overlap_worker(args):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=args.coord,
                               num_processes=args.nproc,
                               process_id=args.proc_id)
    import deepspeed_tpu  # noqa: F401  (gloo-collectives flag first)
    from deepspeed_tpu.runtime import checkpointing as ckpt_io

    steps, rank = args.steps, args.proc_id
    preempt_dir = os.path.join(args.scratch, "preempt_ck")

    if args.phase == "resume":
        # phase 2 of the preemption lane: a relaunched pair resumes
        # from the SIGTERM checkpoint and finishes the run
        tag = f"preempt_step{OVERLAP_PREEMPT_AT + 1}"
        losses, params, _, _ = overlap_lane(
            steps, resume=(preempt_dir, tag, OVERLAP_PREEMPT_AT + 1))
        print("OVL_RANK " + json.dumps({
            "rank": rank, "phase": "resume",
            "losses": [round(x, 8) for x in losses],
            "params_digest": _params_digest(params),
        }), flush=True)
        return

    base_losses, base_params, base_delta, engaged = overlap_lane(steps)
    assert engaged, "overlap did not engage over the socket mesh"
    assert not base_delta.get("exchange.reconnects"), base_delta

    rc_losses, rc_params, rc_delta, _ = overlap_lane(
        steps, faults=overlap_reconnect_rules())
    assert rc_losses == base_losses, (
        f"rank {rank}: reconnect lane diverged "
        f"({base_losses} vs {rc_losses})")
    _assert_params_equal(base_params, rc_params,
                         f"rank {rank} reconnect lane")
    reconnects = rc_delta.get("exchange.reconnects", {}).get("calls", 0)
    resends = rc_delta.get("exchange.resends", {}).get("calls", 0)
    # every injected drop heals through exactly ONE reconnect per rank
    # (the dialer re-dials, the acceptor re-accepts — both count their
    # side once); nothing may escalate to demotion
    n_drops = len(overlap_reconnect_rules())
    assert reconnects == n_drops, (reconnects, rc_delta)
    assert not rc_delta.get("exchange.demotions"), rc_delta

    dm_losses, dm_params, dm_delta, _ = overlap_lane(
        steps,
        comm={"overlap_reconnect_attempts": 0,
              "overlap_reconnect_window_ms": 2000},
        faults=overlap_demotion_rules())
    assert dm_losses == base_losses, (
        f"rank {rank}: demotion lane diverged "
        f"({base_losses} vs {dm_losses})")
    _assert_params_equal(base_params, dm_params,
                         f"rank {rank} demotion lane")
    assert dm_delta.get("exchange.demotions", {}).get("calls") == 1, \
        dm_delta

    # preemption phase 1: both ranks SIGTERM mid-run, save through the
    # real coordination-service commit barrier, exit cleanly
    exited = False
    try:
        overlap_lane(steps, preempt_dir=preempt_dir,
                     sigterm_step=OVERLAP_PREEMPT_AT)
    except SystemExit as e:
        exited = e.code == 0
    assert exited, f"rank {rank}: SIGTERM did not exit cleanly"
    tag = ckpt_io.read_latest_tag(preempt_dir)
    assert tag == f"preempt_step{OVERLAP_PREEMPT_AT + 1}", tag

    print("OVL_RANK " + json.dumps({
        "rank": rank, "phase": "chaos",
        "losses": [round(x, 8) for x in base_losses],
        "params_digest": _params_digest(base_params),
        "reconnects": reconnects,
        "resends": resends,
        "resend_bytes": rc_delta.get("exchange.resends",
                                     {}).get("bytes", 0),
        "demotions": dm_delta.get("exchange.demotions",
                                  {}).get("calls", 0),
        "faults_injected": (
            rc_delta.get("fault.injected", {}).get("calls", 0)
            + dm_delta.get("fault.injected", {}).get("calls", 0)),
        "preempt_tag": tag,
    }), flush=True)


def _launch_overlap_workers(nproc, steps, scratch, phase, timeout):
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # CPU lane, never the chip
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--overlap-worker", "--phase", phase,
             "--proc-id", str(i), "--nproc", str(nproc),
             "--coord", coord, "--steps", str(steps),
             "--scratch", scratch],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for i in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            assert p.returncode == 0, out[-4000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    ranks = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("OVL_RANK "):
                ranks.append(json.loads(line[len("OVL_RANK "):]))
    assert len(ranks) == nproc, outs
    ranks.sort(key=lambda r: r["rank"])
    return ranks


def run_tcp_overlap(nproc=2, steps=8, record=True, scratch=None,
                    timeout=900):
    """The 2-proc TCP overlap campaign over the REAL socket mesh.
    Phase 1 (chaos): fault-free baseline, the reconnect lane (conn
    reset + peer-kill recv fault + CRC-caught corruption, all healed,
    counters pinned, zero demotions), the demotion lane (budget zeroed
    -> completes on the serial wire), and the preemption lane's SIGTERM
    half.  Phase 2 (resume): a relaunched pair resumes from the
    committed emergency tag and must land bitwise-identical final
    params.  Zero supervisor restarts throughout — each phase is one
    launch and every process exits 0."""
    made = scratch is None
    scratch = scratch or tempfile.mkdtemp(prefix="chaos_overlap_tcp_")
    try:
        ranks = _launch_overlap_workers(nproc, steps, scratch, "chaos",
                                        timeout)
        assert all(r["losses"] == ranks[0]["losses"] for r in ranks), ranks
        assert all(r["params_digest"] == ranks[0]["params_digest"]
                   for r in ranks), ranks
        n_drops = len(overlap_reconnect_rules())
        for r in ranks:
            assert r["reconnects"] == n_drops, ranks
            assert r["demotions"] == 1, ranks
        total_resends = sum(r["resends"] for r in ranks)
        # each drop loses the dropping side's in-flight frame (always
        # resent) and MAY lose the peer's concurrent frame (the duplex
        # race: its ACK was or wasn't in flight at teardown) — with the
        # quiescent-wire injection discipline that bounds resends to
        # [drops, 2*drops]; dedup makes the duplicates harmless
        assert n_drops <= total_resends <= 2 * n_drops, \
            (total_resends, ranks)

        resumed = _launch_overlap_workers(nproc, steps, scratch,
                                          "resume", timeout)
        assert all(r["losses"] == resumed[0]["losses"]
                   for r in resumed), resumed
        assert all(r["params_digest"] == ranks[0]["params_digest"]
                   for r in resumed), (
            "resume from the preemption checkpoint diverged from the "
            "uninterrupted run", ranks, resumed)

        result = {
            "metric": f"chaos_overlap_{nproc}proc_tcp",
            "platform": "cpu",
            "world": {"processes": nproc},
            "steps": steps,
            "fault_kinds": ["exchange.send raise (conn reset)",
                            "exchange.recv raise (peer kill)",
                            "exchange.payload corrupt (CRC)"],
            "reconnects_per_rank": ranks[0]["reconnects"],
            "resends_total": total_resends,
            "resend_bytes_total": sum(r["resend_bytes"] for r in ranks),
            "demotions_per_rank": 1,
            "preempt_tag": ranks[0]["preempt_tag"],
            "loss_parity": "exact",
            "resume_parity": "exact",
            "supervisor_restarts": 0,
            "value": n_drops,
            "unit": "wire_faults_healed",
            "ranks": ranks,
        }
        if record:
            from deepspeed_tpu.monitor.artifacts import record_bench_result

            result["artifact"] = record_bench_result(
                result, name=result["metric"])
        return result
    finally:
        if made:
            shutil.rmtree(scratch, ignore_errors=True)


# ---------------------------------------------------------------------------
# elastic shrink-to-survivors campaigns: kill a rank, shrink, grow back,
# lose zero samples (ISSUE 11; docs/tutorials/elasticity.md)
# ---------------------------------------------------------------------------

ELASTIC_BATCH = 24            # divisible by every width in the campaign
ELASTIC_DRY_N = 144           # 6 batches/epoch at B=24
ELASTIC_DRY_TOTAL = 12        # 2 full epochs
ELASTIC_DRY_KILL_AT = 5       # the simulated rank death lands here
ELASTIC_DRY_REGROW_AT = 9     # the shrunken phase hands back here


class _LedgerRegression(_SyntheticRegression):
    """_SyntheticRegression that LOGS every __getitem__ index — the
    sample ledger the exactly-once claim is pinned against.  Lanes run
    with the data pipeline disabled so pulls == trained batches."""

    def __init__(self, n, dim=DIM, out=4, seed=0):
        super().__init__(n, dim=dim, out=out, seed=seed)
        self.log = []

    def __getitem__(self, i):
        self.log.append(int(i))
        return super().__getitem__(i)


def _elastic_env_vars():
    """The elastic env contract, from its single source of truth
    (imported lazily: deepspeed_tpu pulls jax, which launcher-side code
    paths must not)."""
    from deepspeed_tpu.elasticity.elastic_env import ELASTIC_ENV_VARS

    return ELASTIC_ENV_VARS


class _elastic_env:
    """Scoped DSTPU_* elastic env for one in-process phase (the dry run
    plays supervisor: each phase is one incarnation's boot)."""

    def __init__(self, surviving=None, dead=None, incarnation=0,
                 restart=False, reason=None):
        self._want = {
            "DSTPU_SURVIVING_WORLD": (None if surviving is None
                                      else str(surviving)),
            "DSTPU_DEAD_RANKS": (None if not dead else
                                 ",".join(str(r) for r in dead)),
            "DSTPU_INCARNATION": str(incarnation),
            "DSTPU_ELASTIC_RESTART": "1" if restart else None,
            "DSTPU_ELASTIC_REASON": reason,
        }

    def __enter__(self):
        from deepspeed_tpu.runtime.comm.hostwire import set_incarnation

        env_vars = _elastic_env_vars()
        self._saved = {k: os.environ.get(k) for k in env_vars}
        for k in env_vars:
            os.environ.pop(k, None)
        for k, v in self._want.items():
            if v is not None:
                os.environ[k] = v
        set_incarnation(None)  # re-read the env lazily
        return self

    def __exit__(self, *exc):
        from deepspeed_tpu.runtime.comm.hostwire import set_incarnation

        for k, v in self._saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        set_incarnation(None)
        return False


def elastic_dry_lane(dataset, ckpt_dir, until_step, *, resume=False,
                     save=True, monitor_path=None, job_name="elastic"):
    """One incarnation of the dry campaign: boot (under whatever elastic
    env the caller scoped), optionally resume from `ckpt_dir`, train to
    `until_step` off the engine-owned loader, checkpointing each step.
    Returns (losses, counter_delta, run_dir)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.runtime import checkpointing as ckpt_io

    cfg = {
        "train_batch_size": ELASTIC_BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        # pulls == trained batches: the ledger dataset logs consumption
        "data_pipeline": {"enabled": False},
    }
    if monitor_path is not None:
        cfg["monitor"] = {"enabled": True, "output_path": monitor_path,
                          "job_name": job_name, "flush_interval": 1,
                          "flops": False, "heartbeat_interval": 1}
    engine, *_ = ds.initialize(model=_mlp(), config_params=cfg,
                               training_data=dataset,
                               dist_init_required=False)
    snap = COUNTERS.snapshot()
    if resume:
        engine.load_checkpoint(ckpt_dir)
    losses = []
    while engine.global_steps < until_step:
        losses.append(float(engine.train_batch()))
        if save:
            engine.save_checkpoint(ckpt_dir,
                                   tag=f"step{engine.global_steps}")
    ckpt_io.flush_pending()
    delta = COUNTERS.delta_since(snap)
    run_dir = (engine.run_monitor.run_dir
               if engine.run_monitor is not None else None)
    engine.finalize_monitoring()
    return losses, delta, run_dir


def run_dry_elastic(artifact_root=None, record=True, root=None):
    """Tier-1 CPU elastic campaign (in-process, 8 virtual devices):
    kill-simulated rank at dp 4 -> shrink to the 3 survivors -> grow
    back to 4 — with the sample ledger pinned exactly-once and the loss
    ledger pinned against the uninterrupted oracle.

    Lanes (each a fresh engine booted under the env the supervisor
    would export — `plan_world_transition` computes the same shrink/
    regrow the real supervise() loop applies):

      oracle   dp4, 12 steps uninterrupted (2 exact epochs of 144)
      A        dp4, incarnation 0: 5 steps, checkpoint each, "killed"
      D        dp4 resume (same world): remaining 7 steps — loss parity
               EXACT vs the oracle
      B        dp3 shrink (incarnation 1): 4 steps — resharding-on-
               restore, `elastic.shrinks` == 1, parity within
               reduction-order tolerance
      C        dp4 regrow (incarnation 2): 3 steps — `elastic.regrows`
               == 1, ledger + report render both transitions

    The A+B+C sample ledger must equal the oracle's: every one of the
    144 samples consumed exactly twice (once per epoch) — no drops, no
    double-counts across either transition."""
    import numpy as np

    from collections import Counter

    from deepspeed_tpu.elasticity.supervisor import (_ledger_append,
                                                     plan_world_transition)
    from deepspeed_tpu.monitor.report import load_run, render_markdown

    made_root = root is None
    root = root or tempfile.mkdtemp(prefix="chaos_elastic_")
    try:
        ck = os.path.join(root, "ck")
        runs = os.path.join(root, "runs")

        def fresh_data():
            return _LedgerRegression(ELASTIC_DRY_N)

        with _elastic_env(surviving=4):
            oracle_data = fresh_data()
            oracle_losses, _, _ = elastic_dry_lane(
                oracle_data, os.path.join(root, "ck_oracle"),
                ELASTIC_DRY_TOTAL)

        with _elastic_env(surviving=4, incarnation=0):
            a_data = fresh_data()
            a_losses, _, _ = elastic_dry_lane(a_data, ck,
                                              ELASTIC_DRY_KILL_AT)
        assert a_losses == oracle_losses[:ELASTIC_DRY_KILL_AT], \
            "pre-kill lane diverged from the oracle"

        # same-world resume: EXACT parity (saves nothing — lane B must
        # resume from the kill-point tag, not D's later ones)
        with _elastic_env(surviving=4):
            d_data = fresh_data()
            d_losses, d_delta, _ = elastic_dry_lane(
                d_data, ck, ELASTIC_DRY_TOTAL, resume=True, save=False)
        assert d_losses == oracle_losses[ELASTIC_DRY_KILL_AT:], \
            (f"same-world resume must be EXACT: "
             f"{d_losses} vs {oracle_losses[ELASTIC_DRY_KILL_AT:]}")
        assert not d_delta.get("elastic.shrinks") and \
            not d_delta.get("elastic.regrows"), d_delta
        assert Counter(a_data.log + d_data.log) == \
            Counter(oracle_data.log), "same-world resume ledger mismatch"

        # shrink to the 3 survivors (what supervise() would compute)
        to_w, transition = plan_world_transition(
            4, 4, [3], elastic_shrink=True, min_world=1)
        assert (to_w, transition) == (3, "shrink")
        with _elastic_env(surviving=3, dead=[3], incarnation=1,
                          restart=True,
                          reason="rank(s) [3] went quiet first"):
            b_data = fresh_data()
            b_losses, b_delta, _ = elastic_dry_lane(
                b_data, ck, ELASTIC_DRY_REGROW_AT, resume=True)
        assert b_delta.get("elastic.shrinks", {}).get("calls") == 1, \
            b_delta
        assert np.allclose(
            b_losses,
            oracle_losses[ELASTIC_DRY_KILL_AT:ELASTIC_DRY_REGROW_AT],
            rtol=1e-4, atol=1e-6), \
            (f"cross-world resume outside reduction-order tolerance: "
             f"{b_losses} vs "
             f"{oracle_losses[ELASTIC_DRY_KILL_AT:ELASTIC_DRY_REGROW_AT]}")

        # capacity back: grow to the full width
        to_w2, transition2 = plan_world_transition(
            3, 4, [], elastic_shrink=True, min_world=1)
        assert (to_w2, transition2) == (4, "regrow")
        with _elastic_env(surviving=4, incarnation=2, restart=True,
                          reason="capacity restored"):
            c_data = fresh_data()
            c_losses, c_delta, run_dir = elastic_dry_lane(
                c_data, ck, ELASTIC_DRY_TOTAL, resume=True,
                monitor_path=runs, job_name="elastic")
        assert c_delta.get("elastic.regrows", {}).get("calls") == 1, \
            c_delta
        assert np.allclose(c_losses,
                           oracle_losses[ELASTIC_DRY_REGROW_AT:],
                           rtol=1e-4, atol=1e-6), \
            (c_losses, oracle_losses[ELASTIC_DRY_REGROW_AT:])
        # the replicate-over-data-axis fallback must never fire: the
        # padded loader keeps every batch on the sharded path at every
        # width, so a resume can't double-count through replication
        for d in (b_delta, c_delta, d_delta):
            assert not d.get("input.replicated_batches"), d

        # THE claim: across kill -> shrink -> regrow, every sample of
        # every epoch is consumed exactly once — the multiset equals
        # the uninterrupted oracle's (each index exactly twice here)
        ledger = Counter(a_data.log + b_data.log + c_data.log)
        assert ledger == Counter(oracle_data.log), (
            "sample ledger broken across the shrink/grow cycle: "
            f"{len(+(ledger - Counter(oracle_data.log)))} over-consumed, "
            f"{len(+(Counter(oracle_data.log) - ledger))} dropped")
        assert set(ledger.values()) == {2}, ledger

        # the supervisor-side ledger + report: both transitions render
        ledger_path = os.path.join(run_dir, "restarts.jsonl")
        _ledger_append(ledger_path, {
            "t": time.time(), "event": "restart", "attempt": 2,
            "ran_for_s": 1.0, "exit_code": 1,
            "reason": "rank(s) [3] went quiet first",
            "dead_ranks": [3], "backoff_s": 0.05,
            "from_world": 4, "to_world": to_w, "transition": transition,
            "incarnation": 1, "restarts_used": 1})
        _ledger_append(ledger_path, {
            "t": time.time(), "event": "restart", "attempt": 3,
            "ran_for_s": 1.0, "exit_code": 75,
            "reason": "capacity restored", "dead_ranks": [],
            "backoff_s": 0.05, "from_world": to_w, "to_world": to_w2,
            "transition": transition2, "incarnation": 2,
            "restarts_used": 2})
        md = render_markdown(load_run(run_dir))
        assert "Elastic transitions" in md, md
        assert "shrink | 4 → 3" in md and "regrow | 3 → 4" in md, md
        assert "elastic regrows (resumed at a larger dp)" in md, md

        result = {
            "metric": "chaos_elastic_cpu_dryrun",
            "platform": "cpu",
            "steps": ELASTIC_DRY_TOTAL,
            "world_path": [4, 3, 4],
            "kill_at": ELASTIC_DRY_KILL_AT,
            "samples_exactly_once": True,
            "same_world_resume_parity": "exact",
            "cross_world_resume_parity": "reduction-order tolerance",
            "shrinks": 1,
            "regrows": 1,
            "supervisor_restarts": 0,
            "value": 2,
            "unit": "elastic_transitions_survived",
            "losses": [round(x, 6) for x in oracle_losses],
        }
        if record:
            from deepspeed_tpu.monitor.artifacts import record_bench_result

            result["artifact"] = record_bench_result(
                result, root=artifact_root, name=result["metric"])
        return result
    finally:
        from deepspeed_tpu.runtime import resilience

        resilience.install_fault_plan(None)
        resilience.install_retry_policy(None)
        if made_root:
            shutil.rmtree(root, ignore_errors=True)


# -- the real 2-proc TCP shrink lane ----------------------------------------
# supervise() drives a LAUNCHER child; the launcher spawns the jax
# worker processes at whatever world DSTPU_SURVIVING_WORLD dictates,
# reports a dead worker's rank via elastic_report.json, and the
# supervisor's --elastic-shrink policy relaunches the survivors.

ELASTIC_TCP_N = 96            # 4 batches/epoch at B=24
ELASTIC_TCP_TOTAL = 12        # 3 exact epochs
ELASTIC_TCP_KILL_AT = 5       # rank 1 self-kills at this step boundary
ELASTIC_TCP_REGROW_AT = 9     # the shrunken incarnation hands back here


def _elastic_rank(args):
    """One jax worker of the elastic TCP campaign.  Appends one JSON
    line per COMPLETED step to result_rank<r>.jsonl (a killed
    incarnation's in-flight step therefore never pollutes the ledger —
    exactly the batch the resume re-serves), plus a `done` record with
    the incarnation's counter deltas."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    world = args.nproc
    if world > 1:
        jax.distributed.initialize(coordinator_address=args.coord,
                                   num_processes=world,
                                   process_id=args.proc_id)
    import deepspeed_tpu as ds  # noqa: F401  (gloo-collectives flag first)
    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.runtime import checkpointing as ckpt_io

    inc = int(os.environ.get("DSTPU_INCARNATION", "0") or 0)
    ckpt_dir = os.path.join(args.scratch, "ck")
    data = _LedgerRegression(ELASTIC_TCP_N)
    cfg = {
        "train_batch_size": ELASTIC_BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "data_pipeline": {"enabled": False},
    }
    if args.monitor_dir:
        cfg["monitor"] = {"enabled": True,
                          "output_path": os.path.dirname(args.monitor_dir),
                          "job_name": os.path.basename(args.monitor_dir),
                          "flush_interval": 1, "flops": False,
                          "heartbeat_interval": 1}
    if args.kill_rank >= 0:
        cfg["faults"] = {"rules": [
            {"site": "engine.step", "kind": "kill", "exit_code": 173,
             "steps": [ELASTIC_TCP_KILL_AT], "rank": args.kill_rank}]}
    engine, *_ = ds.initialize(model=_mlp(), config_params=cfg,
                               training_data=data,
                               dist_init_required=False)
    snap = COUNTERS.snapshot()
    engine.load_checkpoint(ckpt_dir)  # fresh start just warns
    start = engine.global_steps
    out_path = os.path.join(args.scratch,
                            f"result_rank{args.proc_id}.jsonl")

    def emit(payload):
        with open(out_path, "a") as f:
            f.write(json.dumps(payload) + "\n")
            f.flush()

    emit({"kind": "boot", "rank": args.proc_id, "incarnation": inc,
          "world": world, "start_step": start})
    while engine.global_steps < args.steps:
        step_id = engine.global_steps
        mark = len(data.log)
        loss = float(engine.train_batch())
        engine.save_checkpoint(ckpt_dir, tag=f"step{engine.global_steps}")
        emit({"kind": "step", "rank": args.proc_id, "incarnation": inc,
              "step": step_id, "loss": round(loss, 8),
              "samples": data.log[mark:]})
    ckpt_io.flush_pending()
    delta = COUNTERS.delta_since(snap)
    engine.finalize_monitoring()
    emit({"kind": "done", "rank": args.proc_id, "incarnation": inc,
          "world": world,
          "shrinks": delta.get("elastic.shrinks", {}).get("calls", 0),
          "regrows": delta.get("elastic.regrows", {}).get("calls", 0),
          "replicated": delta.get("input.replicated_batches",
                                  {}).get("calls", 0)})


def _elastic_launcher(args):
    """The supervised child: spawns DSTPU_SURVIVING_WORLD jax workers
    (full width when unset), forwards SIGTERM, and — when a worker dies
    — kills the rest and writes `elastic_report.json` naming the dead
    rank into the monitor dir, then exits nonzero so the supervisor's
    shrink policy takes over.  A shrunken incarnation that reaches its
    step quota exits 75 ("capacity restored, restart me"), which the
    policy reads as a no-dead-ranks failure -> grow back to full."""
    inc = int(os.environ.get("DSTPU_INCARNATION", "0") or 0)
    try:
        world = int(os.environ.get("DSTPU_SURVIVING_WORLD", "")
                    or args.nproc)
    except ValueError:
        world = args.nproc
    until = args.steps if world >= args.nproc else ELASTIC_TCP_REGROW_AT
    coord = f"127.0.0.1:{_free_port()}" if world > 1 else ""
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # CPU lane, never the chip
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--elastic-rank",
             "--proc-id", str(r), "--nproc", str(world),
             "--coord", coord, "--steps", str(until),
             "--scratch", args.scratch, "--monitor-dir", args.monitor_dir,
             "--kill-rank", str(args.kill_rank if inc == 0 else -1)],
            env=env)
        for r in range(world)
    ]

    def forward(signum, _frame):
        for p in procs:
            if p.poll() is None:
                p.send_signal(signum)

    signal.signal(signal.SIGTERM, forward)
    dead_rank = None
    while dead_rank is None and any(p.poll() is None for p in procs):
        for r, p in enumerate(procs):
            rc = p.poll()
            if rc is not None and rc != 0:
                dead_rank = r
                break
        time.sleep(0.1)
    if dead_rank is not None:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        os.makedirs(args.monitor_dir, exist_ok=True)
        with open(os.path.join(args.monitor_dir, "elastic_report.json"),
                  "w") as f:
            json.dump({"dead_ranks": [dead_rank],
                       "reason": f"worker rank {dead_rank} exited "
                       f"{procs[dead_rank].returncode}"}, f)
        return 1
    for p in procs:
        p.wait()
    return 0 if until >= args.steps else 75


def run_tcp_elastic(nproc=2, record=True, scratch=None, timeout=900):
    """The real shrink-to-survivors lane: kill 1 of 2 ranks mid-run ->
    supervise()'s --elastic-shrink relaunches the survivor at world 1
    -> trains on -> exits asking for capacity -> grows back to 2 ->
    finishes.  Assertions: exactly-once sample ledger across all three
    incarnations (3 exact epochs, every sample 3x), same-world prefix
    losses exact vs an uninterrupted 2-proc oracle, cross-world within
    reduction-order tolerance, shrink+regrow counters and ledger
    entries present, and the run report renders both transitions."""
    import numpy as np

    from collections import Counter

    from deepspeed_tpu.elasticity.supervisor import supervise
    from deepspeed_tpu.monitor.report import load_run, render_markdown

    made = scratch is None
    scratch = scratch or tempfile.mkdtemp(prefix="chaos_elastic_tcp_")
    saved_env = {k: os.environ.pop(k, None) for k in _elastic_env_vars()}
    try:
        def read_records(root):
            recs = []
            for r in range(nproc):
                path = os.path.join(root, f"result_rank{r}.jsonl")
                if not os.path.exists(path):
                    continue
                with open(path) as f:
                    for line in f:
                        line = line.strip()
                        if line:
                            recs.append(json.loads(line))
            return recs

        def launcher_cmd(root, monitor_dir, kill_rank):
            return [sys.executable, os.path.abspath(__file__),
                    "--elastic-launcher", "--nproc", str(nproc),
                    "--steps", str(ELASTIC_TCP_TOTAL),
                    "--scratch", root, "--monitor-dir", monitor_dir,
                    "--kill-rank", str(kill_rank)]

        # oracle: uninterrupted 2-proc run (no supervisor, no faults)
        oracle_root = os.path.join(scratch, "oracle")
        os.makedirs(oracle_root, exist_ok=True)
        rc = subprocess.call(launcher_cmd(
            oracle_root, os.path.join(oracle_root, "runs", "elastic"),
            -1), timeout=timeout)
        assert rc == 0, f"oracle launcher exited {rc}"
        oracle = read_records(oracle_root)
        oracle_steps = {e["step"]: e for e in oracle
                        if e["kind"] == "step" and e["rank"] == 0}
        assert sorted(oracle_steps) == list(range(ELASTIC_TCP_TOTAL))

        # the campaign, under the real supervisor
        camp = os.path.join(scratch, "camp")
        monitor_dir = os.path.join(camp, "runs", "elastic")
        os.makedirs(camp, exist_ok=True)
        rc = supervise(
            launcher_cmd(camp, monitor_dir, kill_rank=1),
            max_restarts=5, backoff=0.05, backoff_cap=0.1,
            monitor_dir=monitor_dir, stall_timeout=0.0,
            grace=15.0, poll_interval=0.2,
            elastic_shrink=True, min_world=1, world=nproc)
        assert rc == 0, f"supervised campaign exited {rc}"

        recs = read_records(camp)
        boots = [e for e in recs if e["kind"] == "boot"]
        dones = [e for e in recs if e["kind"] == "done"]
        steps = [e for e in recs if e["kind"] == "step"]
        incs = sorted({e["incarnation"] for e in boots})
        assert incs == [0, 1, 2], boots
        worlds = {e["incarnation"]: e["world"] for e in boots}
        assert worlds == {0: nproc, 1: nproc - 1, 2: nproc}, worlds

        # per-step stream: completed steps only (the killed step 5 was
        # never recorded by incarnation 0 and re-trains in 1) — every
        # step exactly once per RANK of its incarnation, in order
        by_step = {}
        for e in steps:
            by_step.setdefault(e["step"], []).append(e)
        assert sorted(by_step) == list(range(ELASTIC_TCP_TOTAL)), \
            sorted(by_step)
        for s, entries in by_step.items():
            owner_inc = {e["incarnation"] for e in entries}
            assert len(owner_inc) == 1, (s, entries)  # no re-trained step
            # every rank of the incarnation saw the identical global loss
            assert len({e["loss"] for e in entries}) == 1, (s, entries)
            # ... and assembled the identical global batch (the
            # same-value-everywhere device_put contract)
            assert len({tuple(e["samples"]) for e in entries}) == 1, \
                (s, entries)

        # loss parity vs the oracle: incarnation 0 (same world) exact,
        # the shrunken/regrown tail within reduction-order tolerance
        for s in range(ELASTIC_TCP_KILL_AT):
            assert by_step[s][0]["loss"] == oracle_steps[s]["loss"], \
                (s, by_step[s][0]["loss"], oracle_steps[s]["loss"])
        tail = [by_step[s][0]["loss"] for s in
                range(ELASTIC_TCP_KILL_AT, ELASTIC_TCP_TOTAL)]
        otail = [oracle_steps[s]["loss"] for s in
                 range(ELASTIC_TCP_KILL_AT, ELASTIC_TCP_TOTAL)]
        assert np.allclose(tail, otail, rtol=1e-4, atol=1e-6), \
            (tail, otail)

        # THE exactly-once claim, across incarnations: each step's
        # global batch (identical on every rank, asserted above) counted
        # once == every sample of every epoch exactly once (3 exact
        # epochs here)
        ledger = Counter()
        for entries in by_step.values():
            ledger.update(entries[0]["samples"])
        assert set(ledger.values()) == {ELASTIC_TCP_TOTAL * ELASTIC_BATCH
                                        // ELASTIC_TCP_N}, (
            "sample ledger broken across the TCP shrink/grow cycle",
            {k: v for k, v in ledger.items()
             if v != ELASTIC_TCP_TOTAL * ELASTIC_BATCH // ELASTIC_TCP_N})
        assert len(ledger) == ELASTIC_TCP_N, len(ledger)

        # counters: the shrink landed in incarnation 1, the regrow in 2
        inc_done = {e["incarnation"]: e for e in dones}
        assert inc_done[1]["shrinks"] == 1 and \
            inc_done[1]["regrows"] == 0, inc_done[1]
        assert inc_done[2]["regrows"] == 1 and \
            inc_done[2]["shrinks"] == 0, inc_done[2]
        assert all(e["replicated"] == 0 for e in dones), dones

        # supervisor ledger + report: both transitions recorded
        with open(os.path.join(monitor_dir, "restarts.jsonl")) as f:
            ledger_rows = [json.loads(x) for x in f if x.strip()]
        trans = [(r.get("transition"), r.get("from_world"),
                  r.get("to_world")) for r in ledger_rows
                 if r.get("transition")]
        assert ("shrink", nproc, nproc - 1) in trans, trans
        assert ("regrow", nproc - 1, nproc) in trans, trans
        md = render_markdown(load_run(monitor_dir))
        assert "Elastic transitions" in md and "shrink" in md and \
            "regrow" in md, md

        result = {
            "metric": f"chaos_elastic_{nproc}proc_tcp",
            "platform": "cpu",
            "world": {"processes": nproc},
            "steps": ELASTIC_TCP_TOTAL,
            "world_path": [nproc, nproc - 1, nproc],
            "kill": f"rank 1 os._exit(173) at step {ELASTIC_TCP_KILL_AT}",
            "samples_exactly_once": True,
            "same_world_prefix_parity": "exact",
            "cross_world_parity": "reduction-order tolerance",
            "shrinks": 1,
            "regrows": 1,
            "supervisor_restarts": 2,
            "value": 2,
            "unit": "elastic_transitions_survived",
            "losses": [by_step[s][0]["loss"]
                       for s in range(ELASTIC_TCP_TOTAL)],
        }
        if record:
            from deepspeed_tpu.monitor.artifacts import record_bench_result

            result["artifact"] = record_bench_result(
                result, name=result["metric"])
        return result
    finally:
        for k, v in saved_env.items():
            if v is not None:
                os.environ[k] = v
        if made:
            shutil.rmtree(scratch, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nproc", type=int, default=1)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--no-record", action="store_true")
    ap.add_argument("--overlap", action="store_true")
    ap.add_argument("--elastic", action="store_true")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--overlap-worker", dest="overlap_worker",
                    action="store_true")
    ap.add_argument("--elastic-launcher", dest="elastic_launcher",
                    action="store_true")
    ap.add_argument("--elastic-rank", dest="elastic_rank",
                    action="store_true")
    ap.add_argument("--phase", default="chaos",
                    choices=("chaos", "resume"))
    ap.add_argument("--proc-id", dest="proc_id", type=int, default=0)
    ap.add_argument("--coord", default="")
    ap.add_argument("--scratch", default="")
    ap.add_argument("--monitor-dir", dest="monitor_dir", default="")
    ap.add_argument("--kill-rank", dest="kill_rank", type=int, default=-1)
    args = ap.parse_args()
    if args.worker:
        _worker(args)
        return 0
    if args.overlap_worker:
        _overlap_worker(args)
        return 0
    if args.elastic_rank:
        _elastic_rank(args)
        return 0
    if args.elastic_launcher:
        return _elastic_launcher(args)
    if args.elastic and args.nproc > 1:
        result = run_tcp_elastic(nproc=args.nproc,
                                 record=not args.no_record)
    elif args.elastic:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import jax

        jax.config.update("jax_platforms", "cpu")
        result = run_dry_elastic(record=not args.no_record)
    elif args.overlap and args.nproc > 1:
        result = run_tcp_overlap(nproc=args.nproc,
                                 steps=max(8, args.steps),
                                 record=not args.no_record)
    elif args.overlap:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import jax

        jax.config.update("jax_platforms", "cpu")
        result = run_dry_overlap(steps=max(6, args.steps),
                                 record=not args.no_record)
    elif args.nproc <= 1:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import jax

        jax.config.update("jax_platforms", "cpu")
        result = run_dry(steps=max(4, args.steps),
                         record=not args.no_record)
    else:
        result = run_tcp(nproc=args.nproc, steps=args.steps,
                         record=not args.no_record)
    print(json.dumps(result, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
