"""Live candidate probing on a RUNNING engine.

A probe applies a candidate comm config through the same rebuild path
`engine.allreduce_gradients(bucket_size=...)` and the PR-10 runtime
demotion already exercise (BucketPlan + overlap + StepBuilder program
rebuild), then times a few steps — but on COPIES of the training state:

* params/optimizer/scaler are device-copied once per probe (one fused
  jitted copy program, the async-checkpoint snapshot trick), so the
  donated step programs invalidate probe buffers, never the run's
* the probe batch is the last real batch the engine trained on
  (`engine._autotune_batch`, stashed by the forward paths), replayed
  with a FIXED rng — probe steps never consume training data and never
  advance the engine's rng stream
* probe dispatches go through the RAW jitted programs (`CountedFn.fn`,
  the flops-analysis discipline), so `grad_wire.*` per-dispatch
  counters are not bumped by probe traffic; the probe's own cost lands
  in `autotune.probes`
* afterwards the previous build products (plan, step fns, overlap
  mode) are restored BY REFERENCE — the incumbent config's compiled
  programs come back without a recompile

The engine's global_steps / micro_steps / rng / scheduler / monitor
are untouched: a probed run continues bitwise as if the probe never
happened (pinned in tests/test_autotune.py)."""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from ...utils.logging import log_dist
from .space import Candidate

# build products swapped wholesale around a probe; the overlap EXCHANGE
# is deliberately absent — it survives rebuilds by design (engine.
# _build_overlap) and is reused by later probes/swaps
_BUILD_ATTRS = ("bucket_plan", "_overlap_mode", "_step_fns",
                "_overlap_payload_nbytes", "_overlap_matrix_sharding",
                "_qwz_overlap")


def capture_build(engine) -> Dict[str, Any]:
    state = {attr: getattr(engine, attr, None) for attr in _BUILD_ATTRS}
    state["comm_config"] = engine._config.comm_config
    return state


def restore_build(engine, state: Dict[str, Any]) -> None:
    engine._config.comm_config = state["comm_config"]
    for attr in _BUILD_ATTRS:
        setattr(engine, attr, state[attr])
    engine._overlap_pending = []


def apply_candidate(engine, candidate: Candidate) -> None:
    """Re-parse the candidate's comm fragment through the REAL config
    validator (relative to the current config: bucket size, quant block
    and the mesh's factorization are inherited where unspecified), then
    rebuild plan/overlap/step programs — the allreduce_gradients retune
    path, generalized to every live knob."""
    from .. import constants as c
    from ..config import DeepSpeedCommConfig

    if candidate.scope != "live":
        raise ValueError(
            f"candidate {candidate.name!r} is scope={candidate.scope!r}: "
            "the data-axis factorization is the mesh layout and is fixed "
            "at initialize() — rebuild-scope candidates only probe "
            "through an engine factory (tools/autotune_bench.py)")
    cc_old = engine._config.comm_config
    merged = dict(candidate.comm)
    merged.setdefault("reduce_bucket_size", cc_old.reduce_bucket_size)
    merged.setdefault("quant_block_size", cc_old.quant_block_size)
    outer = engine.mesh_info.data_outer_size
    if outer > 1:
        merged.setdefault("hierarchy", {"outer": int(outer)})
    pd: Dict[str, Any] = {"comm": merged}
    if cc_old.fp32_allreduce:
        pd[c.FP32_ALLREDUCE] = True
    new_cc = DeepSpeedCommConfig(pd, engine._config.zero_config,
                                 world_size=engine.dp_world_size)
    # process-global selections made at initialize() carry over: the
    # MoE wire is installed before params placement, and the overlap
    # transport knobs are fabric properties, not search knobs
    new_cc.moe = cc_old.moe
    for k in ("overlap_timeout_ms", "overlap_reconnect_attempts",
              "overlap_reconnect_window_ms", "overlap_keepalive_ms"):
        setattr(new_cc, k, getattr(cc_old, k))

    # settle in-flight overlapped exchanges against the CURRENT plan's
    # combine before it is replaced (the allreduce_gradients invariant:
    # never drop already-dispatched micro gradients)
    engine._drain_overlap()
    engine._config.comm_config = new_cc
    engine.bucket_plan = engine._build_bucket_plan()
    engine._overlap_mode = engine._resolve_overlap()
    engine._build_overlap()
    engine._step_fns = engine._build_step_fns()
    engine._register_exchange_watchdog()
    log_dist(f"autotune: applied {candidate.describe()}", ranks=[0])


class EngineProber:
    """Times candidates on a live engine without touching training
    state.  Construct at a step boundary (no pending micro gradients);
    `probe()` restores the incumbent build before returning."""

    def __init__(self, engine, steps: int = 2, warmup: int = 1):
        if getattr(engine, "_overlap_pending", None):
            raise RuntimeError(
                "autotune probe: in-flight overlapped exchanges — probes "
                "run at step boundaries only")
        if engine._qwz_overlap is not None or engine._offload is not None \
                or engine._infinity is not None:
            raise RuntimeError(
                "autotune live probing covers the device step paths "
                "(stage < 3, no offload/Infinity) — tune those runs "
                "through the engine-factory search instead")
        self.engine = engine
        self.steps = int(steps)
        self.warmup = int(warmup)
        self._copy_fn = None
        batch = getattr(engine, "_autotune_batch", None)
        if batch is None:
            raise RuntimeError(
                "autotune probe: no probe batch stashed yet — run at "
                "least one forward()/train_batch() first (or pass "
                "batch= to autotune_search)")
        self.batch = batch

    # -- state copies ---------------------------------------------------

    def _copies(self):
        import jax
        import jax.numpy as jnp

        # ONE jitted copy program per prober: jit caches by function
        # identity, so a per-call lambda would retrace every probe
        copy = self._copy_fn
        if copy is None:
            copy = self._copy_fn = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t))
        eng = self.engine
        return (copy(eng._params), copy(eng._opt_state),
                copy(eng._scaler_state))

    # -- one probe ------------------------------------------------------

    def probe(self, candidate: Candidate) -> Dict[str, Any]:
        """Apply, time `steps` real engine steps on state copies,
        restore.  Returns {"step_ms", "exposed_ms", "loss", ...}."""
        eng = self.engine
        saved = capture_build(eng)
        try:
            apply_candidate(eng, candidate)
            return self._time_steps()
        finally:
            restore_build(eng, saved)

    def probe_current(self) -> Dict[str, Any]:
        """Time the INCUMBENT config with the same harness — the
        baseline a retune decision compares against (same probe batch,
        same step count, same raw-program dispatch)."""
        return self._time_steps()

    # -- the composition-aware runner -----------------------------------

    def _time_steps(self) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        eng = self.engine
        fns = eng._step_fns
        gas = eng.gradient_accumulation_steps()
        params, opt, scaler = self._copies()
        rng = jax.random.PRNGKey(0)
        theta = jnp.asarray(1.0, jnp.float32)
        lr = eng._step_lr()  # the form the engine dispatches with
        batch = self.batch
        stacked = None
        if "full_scan" in fns:
            stacked = eng._shard_batch_stacked(jax.tree_util.tree_map(
                lambda x: jnp.stack([x] * gas), batch))
            rngs = jax.random.split(rng, gas)

        times = []
        exposed_us_total = 0
        loss = None
        for i in range(self.warmup + self.steps):
            t0 = time.perf_counter()
            exposed_us = 0
            if "full" in fns:
                (params, opt, scaler, loss, _ovf, _gn, _ex) = \
                    fns["full"].fn(params, opt, scaler, batch, rng, lr,
                                   theta)
            elif "full_scan" in fns:
                (params, opt, scaler, loss, _ovf, _gn, _ex) = \
                    fns["full_scan"].fn(params, opt, scaler, stacked,
                                        rngs, lr, theta)
            elif "grads" in fns:
                acc = eng._zero_grad_acc()
                pending = []
                for _m in range(gas):
                    loss, payload = fns["grads"].fn(
                        params, batch, rng, scaler["cur_scale"], theta)
                    pending.append(eng._overlap_submit(payload))
                jax.block_until_ready(loss)
                for ticket in pending:
                    before = ticket.wait_us
                    mat = ticket.wait(eng._overlap_timeout_s)
                    exposed_us += ticket.wait_us - before
                    mdev = jax.device_put(mat, eng._overlap_matrix_sharding)
                    acc = fns["combine"].fn(acc, mdev)
                    eng._retire_ticket(ticket)
                (params, opt, scaler, _z, _ovf, _gn, _ex) = \
                    fns["apply"].fn(params, opt, scaler, acc, lr)
            else:
                acc = eng._zero_grad_acc()
                for _m in range(gas):
                    loss, acc, _ex = fns["micro"].fn(
                        params, acc, batch, rng, scaler["cur_scale"],
                        theta)
                (params, opt, scaler, _z, _ovf, _gn, _ex) = \
                    fns["apply"].fn(params, opt, scaler, acc, lr)
            jax.block_until_ready(loss)
            if i >= self.warmup:
                times.append(time.perf_counter() - t0)
                exposed_us_total += exposed_us
        times.sort()
        step_ms = times[len(times) // 2] * 1e3
        return {
            "step_ms": round(step_ms, 3),
            "exposed_ms": round(exposed_us_total / 1e3
                                / max(1, self.steps), 3),
            "loss": float(loss),
            "gas": gas,
        }
