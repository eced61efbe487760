"""1-bit Adam measurement harness: step time + wire bytes, compressed
vs dense, through the engine's fused step (reference perf twin:
tests/onebit/test_nccl_perf.py, which times NcclBackend's
compressed_allreduce against torch.distributed.all_reduce).

Two distinct questions, answered separately:

1. WIRE BYTES. The reference's NCCL backend packs sign bits (1
   bit/param, twice: worker all_to_all + server allgather) plus fp32
   scales — ~0.28 bit/param of scales at typical chunk sizes, call it
   ~1/13 of the dense 32 bit/param wire. The TPU/XLA path keeps the
   ALGORITHM (two-stage sign compression with both error feedbacks, the
   part 1-bit Adam's convergence proof needs) but XLA has no packed-int1
   collective wire format: sign(c)*scale rides pmean at full compute
   width. Actual wire bytes on ICI are therefore the SAME as dense —
   printed below as measured-program traffic, not a claim of savings.

2. STEP TIME. Whether the compressed step is faster/slower than dense
   end-to-end (it adds sign/scale/error-feedback FLOPs but no wire
   savings, so on ICI it should be ~neutral-to-negative).

Usage: python tools/onebit_bench.py [--steps 30] [--size nano]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _engine(opt_type, model, cfg_base, wire="sign"):
    import deepspeed_tpu

    cfg = dict(cfg_base)
    params = {"lr": 1e-4, "weight_decay": 0.0}
    if opt_type == "OneBitAdam":
        # compression engages after the momentum warmup; too-early
        # freezing destabilizes (the variance estimate is frozen at
        # freeze_step — reference onebit/adam.py warms ~ O(100) steps)
        params["freeze_step"] = 8
        params["wire"] = wire
    cfg["optimizer"] = {"type": opt_type, "params": params}
    engine, *_ = deepspeed_tpu.initialize(model=model, config_params=cfg)
    return engine


def _time_steps(engine, batch, steps):
    # warmup (compile + freeze_step crossing)
    for _ in range(12):
        engine.forward(batch)
        engine.backward()
        engine.step()
    t = []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        loss.block_until_ready()
        t.append(time.perf_counter() - t0)
    return float(np.median(t)), float(loss)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--size", default="nano")
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    import jax

    from deepspeed_tpu.models import GPT, gpt2_config

    dp = len(jax.devices())
    cfg_base = {
        "train_batch_size": dp,
        "zero_optimization": {"stage": 0},
        "mesh": {"data": dp},
        "steps_per_print": 0,
    }
    model_cfg = gpt2_config(args.size, vocab_size=512,
                            max_seq_len=args.seq, dropout=0.0,
                            embed_dropout=0.0)
    n_params = GPT(model_cfg).num_params()
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 512, (dp, args.seq + 1)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])

    runs = [("Adam", "dense"), ("OneBitAdam", "sign"),
            ("OneBitAdam", "int8")]
    results = {}
    for opt, wire in runs:
        engine = _engine(opt, GPT(model_cfg), cfg_base, wire=wire)
        if opt == "OneBitAdam":
            assert getattr(engine, "_onebit_hot", False), \
                "compressed hot path inactive"
        sec, loss = _time_steps(engine, batch, args.steps)
        results[wire] = sec
        print(f"{opt:>12}/{wire:<5}: median step {sec * 1e3:8.2f} ms  "
              f"(loss {loss:.3f})")

    dense_wire = n_params * 4  # fp32 grad allreduce payload per hop
    # int8 two-phase: a2a int8 + allgather int8 + per-owner scales
    int8_wire = n_params * 2 + dp * 8
    ref_packed = n_params / 8 * 2 + n_params / 2048 * 4 * 2  # bits+scales
    print(json.dumps({
        "metric": "compressed_vs_dense_step_time",
        "dense_ms": round(results["dense"] * 1e3, 2),
        "onebit_sign_ms": round(results["sign"] * 1e3, 2),
        "onebit_int8_ms": round(results["int8"] * 1e3, 2),
        "n_params": int(n_params),
        "wire_bytes_dense": int(dense_wire),
        "wire_bytes_sign_on_xla": int(dense_wire),
        "wire_bytes_int8": int(int8_wire),
        "wire_bytes_ref_nccl_packed": int(ref_packed),
        "world_size": dp,
        "platform": jax.default_backend(),
        "note": ("sign compression rides pmean at full width under XLA "
                 "(no wire savings); wire='int8' transmits int8 through "
                 "all_to_all + all_gather — ~2 bytes/param total vs 4+ "
                 "dense, the TPU-native compression that actually cuts "
                 "DCN bytes."),
    }))


if __name__ == "__main__":
    main()
