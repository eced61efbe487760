"""BERT-large pretraining throughput — the reference's HEADLINE benchmark.

BASELINE.md row 1 (reference docs/_tutorials/bert-pretraining.md:387):
BERT-large on 1x V100 at seq 128 -> 64 TFLOPS/GPU, 272 samples/s;
seq 512 -> 53 TFLOPS/GPU, 52 samples/s. This tool runs the SAME model
configuration (24L/1024d/16h MLM+NSP pretraining step, bf16, ZeRO-2)
through the engine and reports samples/s + model TFLOPS side by side
with those numbers — the apples-to-apples comparison bench.py's GPT-2
metric approximates.

Usage (TPU):   python tools/bert_bench.py [--seq 128|512] [--micro N]
CPU smoke:     JAX_PLATFORMS=cpu python tools/bert_bench.py --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax

# reference numbers (1x V100, docs/_tutorials/bert-pretraining.md:387)
REFERENCE = {128: {"tflops": 64.0, "samples_s": 272.0},
             512: {"tflops": 53.0, "samples_s": 52.0}}


def mlm_batch(rng: np.random.RandomState, B: int, S: int, vocab: int):
    """15%-masked MLM batch + NSP labels (reference pretraining recipe)."""
    ids = rng.randint(0, vocab, size=(B, S)).astype(np.int32)
    labels = np.full((B, S), -100, np.int32)
    mask = rng.rand(B, S) < 0.15
    labels[mask] = ids[mask]
    ids[mask] = 103  # [MASK]
    return {"input_ids": ids, "mlm_labels": labels,
            "token_type_ids": np.zeros((B, S), np.int32),
            "nsp_labels": rng.randint(0, 2, size=(B,)).astype(np.int32)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=128, choices=(128, 512))
    ap.add_argument("--micro", type=int, default=0,
                    help="micro batch/chip (0: reference-recipe default)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--attn-impl", default="auto",
                    choices=("auto", "pallas", "xla"),
                    help="A/B the attention path; 'pallas' forces the "
                         "flash kernel even below the auto min-seq gate "
                         "(seq 128)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model/CPU shapes (plumbing check only)")
    args = ap.parse_args()

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import Bert, bert_config

    # stdout must be EXACTLY the result JSON (callers redirect it
    # to a .json artifact) — route the framework logger to stderr
    import logging
    for h in logging.getLogger("deepspeed_tpu").handlers:
        h.setStream(sys.stderr)

    n_dev = jax.device_count()
    if args.smoke:
        cfg = bert_config("bert-base", num_layers=2, num_heads=4, d_model=64,
                          vocab_size=512, max_seq_len=128,
                          attn_impl=args.attn_impl)
        seq, micro, steps = 64, 4, 3
    else:
        cfg = bert_config("bert-large", max_seq_len=args.seq,
                          attn_impl=args.attn_impl)
        # reference seq-128 recipe uses micro 64/GPU on 32 GB V100
        # (bert-pretraining.md); 16 at seq 512
        seq = args.seq
        micro = args.micro or (64 if seq == 128 else 16)
        steps = args.steps

    attn_impl = args.attn_impl

    def build(impl):
        m = Bert(dataclasses.replace(cfg, attn_impl=impl))
        e, *_ = ds.initialize(model=m, config={
            "train_batch_size": micro * n_dev,
            "train_micro_batch_size_per_gpu": micro,
            "bf16": {"enabled": True},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": n_dev},
            "steps_per_print": 0,
        })
        return e

    engine = build(attn_impl)
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(engine.params))
    rng = np.random.RandomState(0)
    batch = mlm_batch(rng, micro * n_dev, seq, cfg.vocab_size)

    def step():
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        return loss

    fell_back = False
    t0 = time.perf_counter()
    try:
        step().block_until_ready()
    except Exception as exc:
        if attn_impl == "xla":
            raise
        # a Mosaic lowering/compile failure on the flash path must not
        # lose the anchor row — re-measure on the XLA path and say so
        print(f"attn_impl={attn_impl} failed ({type(exc).__name__}); "
              f"falling back to xla", file=sys.stderr)
        attn_impl = "xla"
        fell_back = True
        engine = build("xla")
        t0 = time.perf_counter()
        step().block_until_ready()
    compile_s = time.perf_counter() - t0
    step().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step()
    loss.block_until_ready()
    dt = time.perf_counter() - t0

    samples_s = steps * micro * n_dev / dt
    tok_s_chip = samples_s * seq / n_dev
    tflops = 6.0 * n_params * tok_s_chip / 1e12
    out = {"model": "bert-large" if not args.smoke else "bert-smoke",
           "seq": seq, "micro_per_chip": micro, "world": n_dev,
           "params_m": round(n_params / 1e6, 1),
           "samples_per_sec": round(samples_s, 1),
           "samples_per_sec_chip": round(samples_s / n_dev, 1),
           "tflops_per_chip": round(tflops, 2),
           "step_ms": round(dt / steps * 1000, 1),
           "compile_s": round(compile_s, 1),
           "attn_impl": attn_impl,
           "loss": round(float(loss), 4)}
    if fell_back:
        out["attn_impl_fallback"] = True
    ref = REFERENCE.get(seq)
    if ref and not args.smoke:
        out["ref_v100_tflops"] = ref["tflops"]
        out["ref_v100_samples_s"] = ref["samples_s"]
        out["vs_ref_tflops"] = round(tflops / ref["tflops"], 3)
        out["vs_ref_samples"] = round(
            samples_s / n_dev / ref["samples_s"], 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
