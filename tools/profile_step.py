"""Capture an on-device profiler trace of the bench training step.

Host-side timers time the host; attribution must come from the device
profiler. This tool runs the exact bench.py configuration and writes a
jax.profiler trace (XPlane + trace.json.gz viewable in Perfetto /
TensorBoard) covering N steady-state steps.

Usage:  python tools/profile_step.py [--outdir /tmp/tpu_trace] [--steps 5]
        # then: tensorboard --logdir /tmp/tpu_trace   (or upload
        # plugins/profile/*/trace.json.gz to ui.perfetto.dev)
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir", default="/tmp/tpu_trace")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--size", default=None,
                    help="gpt2 size (default: bench.py's choice)")
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--micro", type=int, default=0)
    args = ap.parse_args()

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config

    backend = jax.default_backend()
    n_dev = jax.device_count()
    size = args.size or ("small" if backend != "cpu" else "nano")
    seq = args.seq or (1024 if backend != "cpu" else 128)
    micro = args.micro or (8 if backend != "cpu" else 4)

    cfg = gpt2_config(size, max_seq_len=seq, shard_activations=n_dev > 1)
    engine, *_ = deepspeed_tpu.initialize(model=GPT(cfg), config_params={
        "train_batch_size": micro * n_dev,
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": n_dev},
        "steps_per_print": 0,
    })
    tokens = jax.random.randint(jax.random.PRNGKey(0),
                                (micro * n_dev, seq + 1), 0, cfg.vocab_size)
    batch = (tokens[:, :-1], tokens[:, 1:])

    def step():
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        return loss

    # compile + settle outside the trace
    step().block_until_ready()
    step().block_until_ready()

    os.makedirs(args.outdir, exist_ok=True)
    with jax.profiler.trace(args.outdir):
        t0 = time.perf_counter()
        for _ in range(args.steps):
            loss = step()
        loss.block_until_ready()
        dt = time.perf_counter() - t0
    print(f"traced {args.steps} steps on {backend}: "
          f"{dt / args.steps * 1000:.1f} ms/step -> {args.outdir}")
    print("view: tensorboard --logdir", args.outdir)


if __name__ == "__main__":
    main()
