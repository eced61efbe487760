"""Per-event dispatch cost: interpreted schedule walk vs compiled program.

The channel pipeline executor was measured at ~300 us of
serialized Python per schedule event (12-16% of CPU-mesh step time,
projected ~150 ms/step at 8 stages x 16 micros).  The compiled executor
(runtime/pipe/compiler.py) lowers the canonical walk once into a flat
program of bound closures.  This harness measures what that removes, on
the exact multi-host code path (p2p channels, single process):

* `dispatch` mode (default, the acceptance numbers): stage programs,
  placements, channel transfers, and rng folds are stubbed with host
  no-ops IDENTICALLY for both executors, so the measured time is purely
  the per-event machinery — schedule regeneration + dependency
  re-simulation + isinstance dispatch + counter/mail bookkeeping for the
  interpreted walk, a closure call for the compiled walk.

* `e2e` mode: untouched tiny-model training steps in both modes — the
  end-to-end delta on a real (CPU-mesh) engine, where device compute and
  jit dispatches (identical in both) dilute the machinery win.

Run: python tools/pipe_dispatch_bench.py [--grid] [--e2e] [--json]
Needs no hardware; forces an 8-device CPU mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.runtime.pipe.module import (LayerSpec,  # noqa: E402
                                               PipelineModule)

D, F, MICRO = 64, 128, 4


class Blk:
    def __init__(self, d, f):
        self.d, self.f = d, f

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"a": jax.random.normal(k1, (self.d, self.f)) * 0.05,
                "b": jax.random.normal(k2, (self.f, self.d)) * 0.05}

    def apply(self, p, x, rng=None, train=True):
        return x + jnp.tanh(x @ p["a"]) @ p["b"]


def mse(out, labels):
    return jnp.mean((out - labels) ** 2)


def build_engine(stages, micros):
    mod = PipelineModule([LayerSpec(Blk, D, F) for _ in range(2 * stages)],
                         num_stages=stages, loss_fn=mse)
    engine, *_ = deepspeed_tpu.initialize(
        model=mod, dist_init_required=False, config_params={
            "train_batch_size": MICRO * micros,
            "train_micro_batch_size_per_gpu": MICRO,
            "gradient_accumulation_steps": micros,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "mesh": {"data": 1, "pipe": -1},
            "pipeline": {"use_p2p_channels": True},
            "steps_per_print": 0})
    assert engine._staged and engine._mh
    return engine


def data_iter(micros, seed=0):
    rng = np.random.RandomState(seed)
    return iter([(rng.rand(MICRO, D).astype(np.float32),) * 2
                 for _ in range(micros)])


def stub_engine(engine):
    """Replace every device-touching call with a host no-op — applied
    identically to both executors, so what remains is the per-event
    dispatch machinery itself.  Rebinds the compiled program afterwards
    (bind captures place/plan/fold at bind time)."""
    zero = np.float32(0.0)
    for rt in engine._local.values():
        rt.fwd_j = lambda own, ro, x, rng: x
        rt.loss_j = lambda own, ro, x, labels, rng: zero
        if rt.is_last:
            rt.bwd_j = (lambda rt=rt: lambda own, ro, x, labels, rng,
                        scale, acc, acc_ro: (x, acc, acc_ro))()
        else:
            rt.bwd_j = (lambda rt=rt: lambda own, ro, x, rng, dy, acc,
                        acc_ro: (x, acc, acc_ro))()
        rt.place_batch = lambda x: x
    for chan in list(engine._chan_act.values()) + \
            list(engine._chan_grad.values()):
        chan.transfer = lambda avals, values=None: values
        chan.plan = lambda avals: (lambda v=None: v)
    # per-STEP bookkeeping (tied reduction, optimizer apply, global
    # scalar sync) is one event per batch, not per-event dispatch —
    # no-op it in both executors
    engine._pipe_optimizer_step_mh = lambda: None
    engine._reduce_tied_grads_mh = lambda: None
    orig_fold = jax.random.fold_in
    jax.random.fold_in = lambda key, c: key
    engine._bound_cache.clear()

    def restore():
        jax.random.fold_in = orig_fold
    return restore


def _best_of(fn, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best  # best-of-N: robust against GC/scheduler noise on the
    # shared 1-core box (same convention as bench.py's peak probe)


def measure_dispatch(engine, micros, reps):
    """Time the two executor WALKS themselves (schedule regeneration +
    dependency re-simulation + per-event dispatch for the interpreted
    path; the bound-closure walk for the compiled path).  Per-batch
    setup that both executors share identically — micro-batch fetch,
    rng derivation, the optimizer-step body — is excluded; it is not
    per-event work and e2e mode measures it."""
    mb = list(data_iter(micros))
    engine._mb_cache = [(x, y) for x, y in mb]
    x0 = np.asarray(mb[0][0])
    aval = jax.ShapeDtypeStruct(x0.shape, x0.dtype)
    engine._aval_out = engine._chunk_out_avals(aval)
    engine._batch_key = jax.random.PRNGKey(0)
    n = engine._n_mc

    def interpreted():
        engine._mail_act = {}
        engine._mail_grad = {}
        engine._sent_act_cnt = [0] * n
        engine._sent_grad_cnt = [0] * n
        engine._recv_act_cnt = [0] * n
        engine._recv_grad_cnt = [0] * n
        engine._load_cnt = 0
        streams = engine._pipe_streams()
        engine._arm_step_guards(streams)
        for rt in engine._local.values():
            rt.losses = []
            rt.fwd_count = 0
            rt.bwd_count = 0
        for s, cmd in engine._simulate_order(streams):
            engine._dispatch_mh(s, cmd)

    steps = engine._compiled_steps(aval)

    def compiled():
        engine._tied_pending = 1
        engine._step_pending = 1
        for rt in engine._local.values():
            rt.losses = []
        for f in steps:
            f()

    interpreted(), compiled()  # warm caches
    return _best_of(interpreted, reps), _best_of(compiled, reps)


def measure_e2e(engine, micros, debug, reps):
    engine._debug_schedule = debug
    for _ in range(2):  # compile / bind / warm jnp caches
        engine.train_batch(data_iter(micros))
    batches = [data_iter(micros, seed=r) for r in range(reps)]
    it = iter(batches)
    return _best_of(lambda: engine.train_batch(next(it)), reps)


def bench_config(stages, micros, mode, reps):
    engine = build_engine(stages, micros)
    if mode == "dispatch":
        restore = stub_engine(engine)
        try:
            dt_int, dt_cmp = measure_dispatch(engine, micros, reps)
        finally:
            restore()
    else:
        dt_int = measure_e2e(engine, micros, debug=True, reps=reps)
        dt_cmp = measure_e2e(engine, micros, debug=False, reps=reps)
    n_ev = engine._pipe_prog.n_source_events
    return {"stages": stages, "micros": micros, "mode": mode,
            "events": n_ev,
            "interp_us_per_event": dt_int / n_ev * 1e6,
            "compiled_us_per_event": dt_cmp / n_ev * 1e6,
            "speedup": dt_int / dt_cmp if dt_cmp else float("inf"),
            "interp_step_ms": dt_int * 1e3,
            "compiled_step_ms": dt_cmp * 1e3}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", action="store_true",
                    help="full (2,4,8) stages x (4,16) micros dispatch "
                         "grid (default: 4x16 only)")
    ap.add_argument("--e2e", action="store_true",
                    help="also run the unstubbed end-to-end comparison")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    configs = ([(p, m) for p in (2, 4, 8) for m in (4, 16)]
               if args.grid else [(4, 16)])
    rows = []
    for stages, micros in configs:
        r = bench_config(stages, micros, "dispatch", args.reps)
        rows.append(r)
        print(f"dispatch P={stages} M={micros}: {r['events']} events, "
              f"interpreted {r['interp_us_per_event']:.1f} us/ev, "
              f"compiled {r['compiled_us_per_event']:.2f} us/ev, "
              f"{r['speedup']:.1f}x", flush=True)
    if args.e2e:
        for stages, micros in ([(4, 16)] if not args.grid else configs):
            r = bench_config(stages, micros, "e2e",
                             max(3, args.reps // 4))
            rows.append(r)
            print(f"e2e      P={stages} M={micros}: {r['events']} events, "
                  f"interpreted {r['interp_us_per_event']:.1f} us/ev, "
                  f"compiled {r['compiled_us_per_event']:.1f} us/ev, "
                  f"{r['speedup']:.2f}x", flush=True)
    if args.json:
        print(json.dumps(rows))


if __name__ == "__main__":
    main()
