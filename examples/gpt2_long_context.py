"""Long-context training via sequence parallelism — the beyond-parity
capability (the reference's long-sequence story is block-sparse
attention only). One GPT, three SP implementations:

    --impl ring         exact ring attention (ppermute K/V rotation)
    --impl ring_zigzag  load-balanced causal ring (~2x fewer FLOPs)
    --impl ulysses      all-to-all head resharding (flash kernel intact)

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt2_long_context.py --impl ring_zigzag --seq 1024
"""

from __future__ import annotations

import argparse
import time

from common import print_curve, token_batches  # noqa: E402

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import GPT, gpt2_config


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="ring_zigzag",
                    choices=("ring", "ring_zigzag", "ulysses"))
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--block-q", type=int, default=0,
                    help="bound ring score memory per step (0 = off)")
    args = ap.parse_args()

    n_dev = jax.device_count()
    # largest divisor of the device count <= 4, so the mesh covers
    # every device at any world size
    sp = max(d for d in (1, 2, 3, 4) if n_dev % d == 0)
    dp = n_dev // sp
    cfg = gpt2_config("nano", vocab_size=512, max_seq_len=args.seq,
                      dropout=0.0, embed_dropout=0.0,
                      sequence_parallel=True,
                      sequence_parallel_impl=args.impl,
                      flash_block_q=args.block_q,
                      shard_activations=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg),
        config_params={
            "train_batch_size": 2 * dp,
            "bf16": {"enabled": True},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": dp, "seq": sp},
            "steps_per_print": 0,
        })
    losses, t0 = [], time.perf_counter()
    for batch in token_batches(args.steps, 2 * dp, args.seq, 512):
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        losses.append(float(loss))
    dt = time.perf_counter() - t0
    print_curve(f"gpt2-nano S={args.seq} sp={sp} {args.impl}", losses)
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({args.steps * 2 * dp * args.seq / dt:.0f} tokens/s)")
    assert losses[-1] < losses[0] and np.isfinite(losses).all()


if __name__ == "__main__":
    main()
