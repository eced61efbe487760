"""Measure pipeline bubble + buffer behaviour of the 1F1B engine.

An earlier review flagged that the GPipe bubble (M+P-1)/M was admitted but never
measured. This harness times the TrainSchedule PipelineEngine at varying
micro-batch counts M and fits the tick model t(M) = a·(M + P - 1) + c:
the bubble fraction (P-1)/(M+P-1) falls as M grows, so per-micro-batch
time must approach `a`. It also reports each stage's in-flight buffer
count (TrainSchedule.num_pipe_buffers: ≤ P for 1F1B) against the M
buffers a GPipe schedule holds — the 1F1B memory win.

Run on the CPU mesh: XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
if "--mp-worker" in sys.argv:
    # one of N cooperating processes, 2 virtual devices each — must be
    # set before the jax import below
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
else:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.runtime.pipe.module import (LayerSpec,  # noqa: E402
                                               PipelineModule)
from deepspeed_tpu.runtime.pipe.schedule import TrainSchedule  # noqa: E402


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


def time_engine(stages, micro_batches, d=256, f=1024, micro_size=8,
                reps=5, interleave=1, n_layers=None, use_channels=False):
    mod = PipelineModule([LayerSpec(Blk, d, f)
                          for _ in range(n_layers or stages * 2)],
                         num_stages=stages, loss_fn=mse,
                         interleave=interleave)
    cfg = {
        "train_batch_size": micro_size * micro_batches,
        "train_micro_batch_size_per_gpu": micro_size,
        "gradient_accumulation_steps": micro_batches,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {"data": 1, "pipe": -1},
        "steps_per_print": 0}
    if use_channels:
        cfg["pipeline"] = {"use_p2p_channels": True}
    engine, *_ = deepspeed_tpu.initialize(
        model=mod, config_params=cfg,
        dist_init_required=False)  # no-op unless jax.distributed is up
    assert engine._staged
    assert engine._mh == use_channels
    rng = np.random.RandomState(0)

    def data():
        return iter([(rng.rand(micro_size, d).astype(np.float32),) * 2
                     for _ in range(micro_batches)])

    engine.train_batch(data())  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        engine.train_batch(data())
    dt = (time.perf_counter() - t0) / reps
    bufs = [TrainSchedule(micro_batches, stages, s).num_pipe_buffers()
            for s in range(stages)]
    return dt, bufs


def channel_overhead():
    """Dispatch overhead of the multi-host channel executor (an earlier
    review's point): every process walks the FULL canonical event order and
    syncs GlobalScalars once per step.  Single-process, same model, same
    schedule — the single-controller executor is the compute floor, the
    channel executor's delta is the serialized-dispatch + channel-
    transfer cost.  Event count scales O(stages x micro batches)."""
    P = 4
    print(f"channel-executor dispatch overhead (P={P} stages, "
          f"single process, exact multi-host code path):")
    print(f"{'M':>4} {'controller':>11} {'channels':>10} {'delta':>8} "
          f"{'delta/event':>12}")
    for M in (4, 8, 16):
        dt_sc, _ = time_engine(P, M, use_channels=False)
        dt_ch, _ = time_engine(P, M, use_channels=True)
        # canonical order ~ (fwd + bwd + send/recv pairs) per (stage, mb)
        # + step-level events; count the dominant term
        events = 8 * P * M
        print(f"{M:>4} {dt_sc * 1e3:>9.0f}ms {dt_ch * 1e3:>8.0f}ms "
              f"{(dt_ch - dt_sc) * 1e3:>6.0f}ms "
              f"{(dt_ch - dt_sc) / events * 1e6:>10.0f}us")


def mp_worker(argv):
    """Times the same tied-weight pipeline the multi-host parity tests
    prove correct (tests/pipe_parity_common.py) — tiny compute, so the
    step time is dispatch + channel transfer dominated: the overhead
    upper bound the table wants."""
    proc_id, nprocs, coord, steps = (int(argv[0]), int(argv[1]), argv[2],
                                     int(argv[3]))
    jax.config.update("jax_threefry_partitionable", True)
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=proc_id)
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "tests"))
    from pipe_parity_common import M, build_module, config, data

    engine, *_ = deepspeed_tpu.initialize(
        model=build_module(num_stages=nprocs), dist_init_required=False,
        config_params=config(use_channels=True))
    assert engine._mh
    engine.train_batch(iter(data(0, M)))  # compile
    t = []
    for s in range(steps):
        t0 = time.perf_counter()
        engine.train_batch(iter(data(1 + s, M)))
        t.append(time.perf_counter() - t0)
    if proc_id == 0:
        dt = float(np.median(t))
        print(f"MPBUBBLE procs={nprocs} M={M} step_ms={dt * 1e3:.1f} "
              f"ms_per_micro={dt / M * 1e3:.1f}", flush=True)


def mp_overhead():
    """Wall time per step of the channel executor at 2 and 4 REAL
    processes (localhost TCP).  On this 1-core box the processes contend
    for the CPU, so treat these as upper bounds on dispatch+transfer
    overhead, not fabric numbers."""
    import socket
    import subprocess

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    for nprocs in (2, 4):
        coord = f"127.0.0.1:{free_port()}"
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["JAX_PLATFORMS"] = "cpu"  # CPU lane: no child reaches for the chip
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--mp-worker",
             str(i), str(nprocs), coord, "5"],
            stdout=subprocess.PIPE if i == 0 else subprocess.DEVNULL,
            stderr=subprocess.STDOUT if i == 0 else subprocess.DEVNULL,
            env=env) for i in range(nprocs)]
        out, _ = procs[0].communicate(timeout=1800)
        rcs = [procs[0].returncode] + [p.wait(timeout=120)
                                       for p in procs[1:]]
        lines = [ln for ln in out.decode().splitlines() if "MPBUBBLE" in ln]
        if any(rcs) or not lines:
            # a silent empty run would read as a measurement — fail loud
            sys.stderr.write(out.decode()[-3000:] + "\n")
            raise RuntimeError(
                f"mp_overhead: workers failed (rcs={rcs}, "
                f"{len(lines)} result lines)")
        for ln in lines:
            print(ln)


def main():
    if "--mp-worker" in sys.argv:
        mp_worker(sys.argv[sys.argv.index("--mp-worker") + 1:])
        return
    if "--channels" in sys.argv:
        channel_overhead()
        return
    if "--mp" in sys.argv:
        mp_overhead()
        return
    P = 4
    print(f"stages={P}; t(M) should scale with (M + P - 1) ticks")
    print(f"{'M':>4} {'s/batch':>9} {'s/micro':>9} {'bubble%':>8} "
          f"{'1f1b bufs':>10} {'gpipe bufs':>10}")
    rows = []
    for M in (2, 4, 8, 16):
        dt, bufs = time_engine(P, M)
        bubble = (P - 1) / (M + P - 1) * 100
        rows.append((M, dt))
        print(f"{M:>4} {dt:>9.3f} {dt / M:>9.3f} {bubble:>7.1f}% "
              f"{str(bufs):>10} {M:>10}")
    # fit t = a*(M+P-1): per-tick cost should be ~constant
    ticks = np.array([m + P - 1 for m, _ in rows], float)
    times = np.array([t for _, t in rows], float)
    a = float(np.dot(ticks, times) / np.dot(ticks, ticks))
    resid = float(np.max(np.abs(times - a * ticks) / times))
    print(f"per-tick fit a={a * 1000:.1f} ms, max residual {resid:.1%} "
          f"(small residual => wall time follows the tick model; "
          f"bubble shrinks as (P-1)/(M+P-1))")

    # interleaved virtual stages: same model depth, bubble /v
    print(f"\ninterleaved 1F1B (P=2 physical stages, same total layers): "
          f"theoretical bubble (P-1)/(v*M+P-1)")
    print(f"{'v':>3} {'M':>4} {'s/batch':>9} {'s/micro':>9} {'bubble%':>8}")
    for v in (1, 2):
        for M in (4, 8):
            # SAME total depth (8 layers) for every v — only the chunking
            # changes, so s/micro differences are schedule, not model
            dt, _ = time_engine(2, M, interleave=v, n_layers=8)
            bubble = (2 - 1) / (v * M + 2 - 1) * 100
            print(f"{v:>3} {M:>4} {dt:>9.3f} {dt / M:>9.3f} "
                  f"{bubble:>7.1f}%")


if __name__ == "__main__":
    main()
