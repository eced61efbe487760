"""First proof that the system starts on the chip: train and serve GPT-2
xl widths through the normal entry points, on one TPU.

    python chip_smoke.py             # one chip: device, train, serve, kernels
    python chip_smoke.py --chips 4   # four chips: ZeRO-2 over data=4 against
                                     # the same steps on one device, nothing else

One process, no children: the process that touches JAX holds the chip.
Every phase prints one JSON object; the LAST line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`
and the exit code is 0 only if every phase passed.  Without a TPU the
device check fails first: there is no CPU mode (tests/test_chip_smoke.py
rehearses the phases at toy size through the functions below).

Weights and tokens come from `SEED`; nothing outside the checkout is
read.  Times printed here are labelled `smoke` and are not results: the
script reports no rate, no utilization and no peak.
"""

from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import os
import re
import sys
import tempfile
import time
import types
import traceback

import numpy as np

SEED = 0

# Published GPT-2 xl widths (models/gpt.py GPT2_SIZES["xl"]): d_model 1600,
# 25 heads of 64, d_ff 6400, vocab 50304, seq 1024.  Depth is the only
# cut: the 48-layer model's ZeRO-2 state does not fit one chip's 16 GB.
# Memory would allow ~32 layers at micro batch 4 (8.65 GB peak at 20,
# 0.38 GB a layer, on the chip); 20 is what keeps the step program, the
# serving programs and the eight oracle programs together under the chip
# tool's 192 MiB compile-cache cap, so that a second run compiles
# nothing (PERF.md, Findings PR 22).
MODEL_SIZE = "xl"
SEQ = 1024
TRAIN_DEPTH = 20
TRAIN_MICRO = 4
TRAIN_STEPS = 5
SERVE_DEPTH = 48

# ZeRO-2 against ZeRO-0 loss parity, as tier-1 holds it on the CPU mesh in
# fp32 (tests/test_engine.py::test_zero_stages_converge_identically).  On
# the chip it is held where both runs have the same weights: the first
# step.  After that two correct bf16 runs drift apart — Adam's first
# updates are sign-like, so rounding noise in near-zero gradients flips
# them — and no tier-1 test bounds that: four chips against one measured
# 6e-7, 8e-6, 5.5e-5, 3.6e-5, 1.3e-6, 5.3e-4 relative over six steps, and
# against one device taking the batch as four micro batches 2.8e-4 at the
# sixth (chip runs, PR 22).  BF16_DRIFT is that measurement with headroom,
# not a tier-1 tolerance.
ZERO_PARITY = dict(rtol=2e-4, atol=1e-5)
BF16_DRIFT = dict(rtol=2e-3, atol=0)


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def cache_entries(path: str) -> set:
    """Names of the compiled programs in the cache directory."""
    if not os.path.isdir(path):
        return set()
    return {n for n in os.listdir(path)
            if not n.endswith("-atime") and not n.startswith(".")}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase(chips: int, cache_dir: str) -> dict:
    """Stop unless JAX found `chips` TPU devices; name what it found."""
    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found platform "
            f"{devices[0].platform!r} ({len(devices)} device(s))")
    if len(devices) != chips:
        raise RuntimeError(
            f"chip_smoke --chips {chips} found {len(devices)} device(s)")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    return {"phase": "device", "platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "compile_cache": cache_dir,
            "cache_entries_at_start": len(cache_entries(cache_dir))}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_config(micro: int, n_dev: int) -> dict:
    """ZeRO-2, bf16 compute, fp32 masters, Adam."""
    return {
        "train_batch_size": micro * n_dev,
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": n_dev},
        "steps_per_print": 0,
    }


def make_batch(model_cfg, global_batch: int, seq: int):
    import jax

    tokens = jax.random.randint(jax.random.PRNGKey(SEED),
                                (global_batch, seq + 1), 0,
                                model_cfg.vocab_size)
    return tokens[:, :-1], tokens[:, 1:]


def build_engine(model_cfg, micro: int, mesh_info):
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT

    n_dev = mesh_info.axis_size("data")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(model_cfg), config_params=train_config(micro, n_dev),
        mpu=mesh_info)
    return engine


def run_steps(engine, batch, steps: int):
    """One warm-up step (compiles), then `steps` on the same batch.
    -> (losses incl. warm-up, set-up seconds, seconds of the rest)."""
    def step():
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        return float(loss)

    t0 = time.perf_counter()
    losses = [step()]
    t1 = time.perf_counter()
    losses += [step() for _ in range(steps)]
    return losses, t1 - t0, time.perf_counter() - t1


def lowered_step(engine, batch):
    """The fused step program, lowered (not compiled) with the
    arguments `engine.forward` dispatches it with."""
    import jax.numpy as jnp

    args = (engine._params, engine._opt_state, engine._scaler_state,
            engine._shard_batch(batch), engine._next_rng(),
            engine._step_lr(), jnp.asarray(1.0, jnp.float32))
    return engine._step_fns["full"].fn.lower(*args)


def check_losses(losses) -> None:
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a repeated batch: {losses}")


def gpt_config(depth: int, seq: int, n_dev: int, size: str = MODEL_SIZE,
               **over):
    from deepspeed_tpu.models import gpt2_config

    return gpt2_config(size, num_layers=depth, max_seq_len=seq,
                       shard_activations=n_dev > 1, **over)


def train_phase(size=MODEL_SIZE, depth=TRAIN_DEPTH, seq=SEQ,
                micro=TRAIN_MICRO, steps=TRAIN_STEPS,
                on_chip=True, **over) -> dict:
    import jax

    from deepspeed_tpu.comm import make_mesh
    from deepspeed_tpu.ops import pallas_backend

    cfg = gpt_config(depth, seq, 1, size, **over)
    engine = build_engine(cfg, micro, make_mesh(devices=jax.devices()[:1]))
    batch = make_batch(cfg, micro, seq)
    flash_in_step = "tpu_custom_call" in lowered_step(engine, batch).as_text()
    if on_chip and (pallas_backend.interpret() or not flash_in_step):
        # attention()'s `auto` gives way to XLA attention without a word
        raise RuntimeError(
            f"the flash kernel is not in the lowered step "
            f"(interpret={pallas_backend.interpret()}, "
            f"tpu_custom_call={flash_in_step})")
    losses, setup_s, run_s = run_steps(engine, batch, steps)
    check_losses(losses)
    stats = jax.devices()[0].memory_stats() or {}
    return {"phase": "train", "d_model": cfg.d_model,
            "heads": cfg.num_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "seq": seq,
            "depth": depth, "depth_published": 48, "micro_batch": micro,
            "params": engine.module.num_params(), "zero_stage": 2,
            "compute": "bf16", "flash_in_lowered_step": flash_in_step,
            "pallas_interpret": pallas_backend.interpret(),
            "losses": losses,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "setup_seconds_smoke": round(setup_s, 2),
            "step_seconds_smoke": round(run_s / steps, 4)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

# what a one-chip deployment of this model would run with: 16 decode
# slots, requests up to the model's whole 1024-token context, and a pool
# that holds all 16 slots at half of it (2.4 GB of bf16 K/V at 48 layers)
SERVE = dict(block_size=16, max_batch=16, max_seq_len=1024,
             prefill_chunk=256, num_blocks=1 + 16 * (512 // 16))
PROMPT_LENS = (12, 37, 64, 100, 150, 200, 300, 420)  # 300, 420 > chunk
MAX_NEW = 16
# bf16 weights and rows move a logit by 0.01-0.03 and the largest two lie
# 0.17 apart on average (PERF.md, PR 24), so two compiled programs may
# break a near tie differently: where the served first token is not
# generate()'s, the model's own logit for it must lie this close to its
# largest (the benchmark cell's `logit_margin`)
FIRST_TOKEN_MARGIN = 0.1
# what a recorder attached to the engine has to have seen of its thread
SERVE_PHASES = ("serve.admit", "serve.prefill.launch", "serve.decode.launch",
                "serve.read", "serve.bookkeep")


def serve_phase(size=MODEL_SIZE, depth=SERVE_DEPTH, seq=SEQ, serve=None,
                prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                margin=FIRST_TOKEN_MARGIN, **over) -> dict:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import GPT
    from deepspeed_tpu.models.generation import generate
    from deepspeed_tpu.monitor.tracing import TraceRecorder
    from deepspeed_tpu.serving import FINISHED, ServeConfig, ServeEngine

    serve = dict(SERVE if serve is None else serve)
    over.setdefault("param_dtype", jnp.bfloat16)
    cfg = gpt_config(depth, seq, 1, size, **over)
    model = GPT(cfg)
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(SEED))
    engine = ServeEngine(model, params, ServeConfig(**serve))
    rs = np.random.RandomState(SEED)
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).tolist()
               for n in prompt_lens]
    if not any(n > serve["prefill_chunk"] for n in prompt_lens):
        raise RuntimeError("no prompt is longer than prefill_chunk")

    # requests join while others decode: three waves, steps in between;
    # a recorder listens to what the engine's thread says it is doing
    waves = [prompts[:3], prompts[3:6], prompts[6:]]
    reqs, joined_while_decoding = [], 0
    with tempfile.TemporaryDirectory() as spans:
        recorder = TraceRecorder(spans, buffer_events=1 << 14)
        engine.attach_tracing(tracer=recorder)
        try:
            for wave in waves:
                decoding = len(engine.scheduler.running())
                for p in wave:
                    reqs.append(engine.submit(p, max_new))
                    joined_while_decoding += decoding > 0
                for _ in range(4):
                    engine.step()
            engine.run()
        finally:
            recorder.close()
        said = {e["name"] for e in recorder.last_events()}
    serve_s = time.perf_counter() - t0
    if not said >= set(SERVE_PHASES):  # the sites fire under this backend
        raise RuntimeError(f"the engine's thread never said "
                           f"{sorted(set(SERVE_PHASES) - said)}")
    for r in reqs:
        if r.state != FINISHED or len(r.out) != max_new:
            raise RuntimeError(
                f"request {r.rid}: state {r.state}, {len(r.out)} of "
                f"{max_new} tokens ({r.error})")
    if joined_while_decoding == 0:
        raise RuntimeError("no request was admitted while others decoded")

    # the oracle tests/test_serving.py pins at toy size: one request at
    # a time through models.generation.generate, same cache length
    t0 = time.perf_counter()
    want = [np.asarray(generate(model, params,
                                np.asarray([p], np.int32), max_new,
                                cache_len=serve["max_seq_len"]))[0].tolist()
            for p in prompts]
    oracle_s = time.perf_counter() - t0
    first_ok = [r.out[0] == w[0] for r, w in zip(reqs, want)]
    agree = sum(a == b for r, w in zip(reqs, want)
                for a, b in zip(r.out, w))
    # a first token that differs may only be the other side of a near tie
    first_gap = [0.0] * len(reqs)
    for i, (r, p) in enumerate(zip(reqs, prompts)):
        if not first_ok[i]:
            last = model.apply(params, np.asarray([p], np.int32))[0, -1]
            last = np.asarray(last, np.float32)
            first_gap[i] = float(last.max() - last[r.out[0]])
    if max(first_gap) > margin:
        raise RuntimeError(
            f"first generated token differs from generate() beyond a "
            f"near tie (margin {margin}): {first_ok}, the model's logit "
            f"for it below its largest by {first_gap}")
    return {"phase": "serve", "d_model": cfg.d_model,
            "heads": cfg.num_heads, "depth": depth, "depth_published": 48,
            "weights": str(jnp.dtype(cfg.param_dtype)),
            "kv_dtype": str(engine.kv.describe()), **serve,
            "requests": len(reqs), "prompt_lens": list(prompt_lens),
            "max_new_tokens": max_new,
            "joined_while_decoding": joined_while_decoding,
            "engine_steps": engine.steps,
            "first_token_equals_generate": first_ok,
            "first_token_gap_to_top_logit": first_gap,
            "token_agreement_share": agree / (len(reqs) * max_new),
            "serve_seconds_smoke": round(serve_s, 2),
            "oracle_seconds_smoke": round(oracle_s, 2)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


# a small EvaByte (the family's own layer kinds at widths the chip tiles:
# heads of 128, chunk 16) served in bf16 through windows that close in
# prefill and in decode.  EVABYTE_GAP bounds how far below the float32
# reference's best logit a byte the engine chose may lie: bf16 weights,
# rows and summaries against float32 `highest` (the chip read 0.0022 over
# 48 positions, top-1 agreement 97.9 %, chip run PR 29; the limit is four
# times that, and far below what a dropped remote term or a value summary
# out of place reads at the published widths, 3.4 and 0.44, PERF.md).
EVABYTE = dict(num_layers=2, num_heads=4, d_model=512, d_ff=1408,
               window_size=256, chunk_size=16, pool_std=4.0)
EVABYTE_SERVE = dict(block_size=16, max_batch=3, max_seq_len=1024,
                     prefill_chunk=128, num_blocks=1 + 3 * (16 + 4),
                     prefix_cache=False)
EVABYTE_PROMPTS = (700, 512, 90)
EVABYTE_GAP = 0.01


def evabyte_phase(model=None, serve=None, prompt_lens=EVABYTE_PROMPTS,
                  max_new=MAX_NEW, gap=EVABYTE_GAP, **over) -> dict:
    """EvaByte served natively against its plain reference: at every
    generated position the byte the engine chose must have a reference
    logit within `gap` of the reference's largest."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import evabyte as reference
    from deepspeed_tpu.models import EvaByte, EvaByteConfig
    from deepspeed_tpu.serving import FINISHED, ServeConfig, ServeEngine

    serve = dict(EVABYTE_SERVE if serve is None else serve)
    over.setdefault("param_dtype", jnp.bfloat16)
    cfg = EvaByteConfig(**{**(EVABYTE if model is None else model),
                           "max_seq_len": serve["max_seq_len"], **over})
    net = EvaByte(cfg)
    t0 = time.perf_counter()
    params = jax.jit(net.init)(jax.random.PRNGKey(SEED))
    engine = ServeEngine(net, params, ServeConfig(**serve))
    free = engine.kv.free_blocks
    rs = np.random.RandomState(SEED)
    reqs = [engine.submit(rs.randint(0, cfg.vocab_size, (n,)).tolist(),
                          max_new) for n in prompt_lens]
    engine.run()
    serve_s = time.perf_counter() - t0
    if any(r.state != FINISHED or len(r.out) != max_new for r in reqs):
        raise RuntimeError(f"unfinished: {[r.state for r in reqs]}")
    if engine.kv.free_blocks != free:
        raise RuntimeError("the free list is not whole after the run")
    t0 = time.perf_counter()
    kw = dict(heads=cfg.num_heads, eps=cfg.rms_norm_eps,
              theta=cfg.rope_theta, window=cfg.window_size,
              chunk=cfg.chunk_size, vocab=cfg.vocab_size)
    worst, agree = 0.0, 0
    for r in reqs:
        lg = np.asarray(reference.logits(
            params, jnp.asarray([r.prompt + r.out]), **kw))[0]
        rows = lg[len(r.prompt) - 1:len(r.prompt) - 1 + max_new]
        chosen = np.asarray(r.out)
        worst = max(worst, float(
            (rows.max(-1) - rows[np.arange(max_new), chosen]).max()))
        agree += int((rows.argmax(-1) == chosen).sum())
    if not worst <= gap:
        raise RuntimeError(
            f"a byte the engine chose lies {worst} below the reference's "
            f"best logit (limit {gap})")
    return {"phase": "evabyte", "d_model": cfg.d_model,
            "heads": cfg.num_heads, "depth": cfg.num_layers,
            "window": cfg.window_size, "chunk": cfg.chunk_size,
            "weights": str(jnp.dtype(cfg.param_dtype)), **serve,
            "prompt_lens": list(prompt_lens), "max_new_tokens": max_new,
            "window_closes": sum((n + max_new - 1) // cfg.window_size
                                 for n in prompt_lens),
            "worst_gap_to_top_logit": worst, "gap_limit": gap,
            "top1_agreement": agree / (len(reqs) * max_new),
            "seconds_serve": round(serve_s, 1),
            "seconds_reference": round(time.perf_counter() - t0, 1)}


def _max_err(got, want) -> float:
    import jax

    return max(float(np.max(np.abs(np.asarray(g, np.float32)
                                   - np.asarray(w, np.float32))))
               for g, w in zip(jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want)))


def _close(name, shape, got, want, rtol, atol) -> dict:
    import jax

    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol, err_msg=name)
    return {"kernel": name, "shape": shape, "max_abs_err": _max_err(got, want),
            "rtol": rtol, "atol": atol}


def kernels_phase(batch=TRAIN_MICRO, seq=SEQ, heads=25, head_dim=64,
                  d_model=1600, vocab=50304,
                  paged_shapes=((25, 64), (16, 128)),
                  paged_slots=16, paged_width=64,
                  bert_batch=16, bert_seq=512, bert_heads=16,
                  eva_heads=(32, 128), eva_window=2048, eva_chunk=16,
                  eva_summary_blocks=64,
                  eva_positions=(0, 100, 2047, 2048, 9000, 12345, 16383, -1),
                  grouped_shapes=((64, 32, 8, 64, 128, 1 / 64),
                                  (16, 128, 8, 128, 1024, None),
                                  (16, 16, 2, 256, 832, None)),
                  sliding_shape=(128, 8, 128, 288, 4096),
                  sliding_positions=(20000, 300, 4095, 4096, 4607, 4608,
                                     9215, -1),
                  prefill_shape=(128, 8, 128, 1024, 512),
                  prefill_starts=(0, 3003, 16084),
                  sliding_chunk_shape=(128, 8, 128, 288, 4096, 512),
                  sliding_chunk_starts=(0, 3584, 4096, 4608, 7003, 20480),
                  latent_shape=(32, 16, 512, 64, 256),
                  routed_shape=(32, 64, 2048, 1408), routed_live=12,
                  slab_shapes=((512, 10, 64, 512, 2048, 512),
                               (512, 8, 16, 128, 4096, 4096)),
                  ssm_shape=(64, 64, 64, 128), ssm_live=37,
                  gdn_shape=(48, 32, 128), gdn_live=29,
                  relu2_shape=(40, 16, 2688, 1856), relu2_live=2,
                  relu2_slab=(512, 6, 16, 128, 2688, 1856),
                  ssm_groups_shape=(40, 64, 64, 128, 8), ssm_groups_live=24,
                  masked_shape=(512, 64, 192, 64, 256, 512),
                  masked_table=(16, 1536, 1024, 2048), masked_start=4608,
                  conv_shape=(96, 2048, 3, 512), conv_live=64,
                  held_shape=(96, 4, 8, 64, 2048, 1536), held_live=64,
                  held_slab=(512, 4, 8, 64, 2048, 1536),
                  on_chip=True) -> dict:
    """Each kernel `auto` selects on this chip, once, natively, at the
    main path's shapes, against its jnp oracle at tier-1's tolerance
    (tests/test_flash_attention.py, test_fused_xent.py, test_kernels.py:
    fp32 operands; the oracle's matmuls at full precision)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.kernels import registry
    from deepspeed_tpu.kernels.eva import (eva_attention_reference,
                                           live_blocks)
    from deepspeed_tpu.kernels.paged import paged_attention_reference
    from deepspeed_tpu.ops import pallas_backend
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    from deepspeed_tpu.ops.transformer.flash_attention import (
        _keep_mask, derive_seed, flash_attention)
    from deepspeed_tpu.ops.transformer.fused_xent import \
        fused_softmax_xent_sum
    from deepspeed_tpu.serving.kv_cache import pool_rows

    if on_chip and pallas_backend.interpret():
        raise RuntimeError("kernels would run under the Pallas interpreter")
    key = jax.random.split(jax.random.PRNGKey(SEED), 8)
    out = []
    with jax.default_matmul_precision("highest"):
        # flash attention, forward and backward
        shape = (batch, seq, heads, head_dim)
        q, k, v = (jax.random.normal(key[i], shape, jnp.float32)
                   for i in range(3))
        out.append(_close(
            "flash_attention_fwd", list(shape),
            jax.jit(lambda *a: flash_attention(*a, causal=True))(q, k, v),
            jax.jit(lambda *a: xla_attention(*a, causal=True))(q, k, v),
            rtol=2e-5, atol=2e-5))

        def grads(attn, **kw):  # of the q, k, v bound when it is called
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(attn(q, k, v, **kw) ** 2),
                argnums=(0, 1, 2)))(q, k, v)

        out.append(_close("flash_attention_bwd", list(shape),
                          grads(flash_attention, causal=True),
                          grads(xla_attention, causal=True),
                          rtol=1e-3, atol=1e-3))

        # the variants no benchmark cell runs: bidirectional, key bias
        # (BERT's padding mask) and in-kernel dropout at BERT-large's
        # seq-512 shape, against a plain softmax under the SAME mask
        # (the kernel's own hash over global indices, whole plane)
        shape = (bert_batch, bert_seq, bert_heads, head_dim)
        q, k, v = (jax.random.normal(key[i], shape, jnp.float32)
                   for i in range(3))
        lens = np.random.RandomState(SEED).randint(
            bert_seq // 2, bert_seq + 1, size=bert_batch)
        bias = jnp.asarray(np.where(
            np.arange(bert_seq)[None] < lens[:, None], 0.0, -1e30)
            [:, None, None, :], jnp.float32)
        rate, rng = 0.1, jax.random.PRNGKey(SEED + 1)
        seed = derive_seed(rate, rng)[0][0]
        zero = jnp.int32(0)
        plane = jax.jit(jax.vmap(lambda bh: _keep_mask(
            seed, bh, zero, zero, bert_seq, bert_seq, rate)))(
            jnp.arange(bert_batch * bert_heads, dtype=jnp.int32)).reshape(
            bert_batch, bert_heads, bert_seq, bert_seq)

        def masked_ref(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
            p = jax.nn.softmax(s + bias, axis=-1) * plane
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        def kernel(q, k, v):
            return flash_attention(q, k, v, causal=False, key_bias=bias,
                                   dropout_rate=rate, dropout_rng=rng)

        out.append(_close("flash_attention_full_bias_dropout_fwd",
                          list(shape), jax.jit(kernel)(q, k, v),
                          jax.jit(masked_ref)(q, k, v),
                          rtol=2e-5, atol=2e-5))
        out.append(_close("flash_attention_full_bias_dropout_bwd",
                          list(shape), grads(kernel), grads(masked_ref),
                          rtol=2e-3, atol=2e-3))

        # fused projection + cross-entropy, forward and backward
        n = batch * seq
        x = jax.random.normal(key[3], (n, d_model), jnp.float32)
        w = jax.random.normal(key[4], (d_model, vocab), jnp.float32) * 0.02
        labels = jax.random.randint(key[5], (n,), 0, vocab)
        valid = jnp.ones((n,), bool)

        def xent_ref(x, w):
            logits = (x @ w).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
            return jnp.sum(jnp.where(valid, lse - ll, 0.0)) / n

        def xent_fused(x, w):
            # fp32 operands (tier-1's, for its tolerance) take twice
            # bf16's VMEM: the chip refused the bf16 path's blocks of
            # (256, 384) for 20.1 MB of its 16 MB, and (256, 512) for 25.0
            return fused_softmax_xent_sum(x, w, labels, valid,
                                          256, 128) / n

        got = jax.jit(jax.value_and_grad(xent_fused, argnums=(0, 1)))(x, w)
        want = jax.jit(jax.value_and_grad(xent_ref, argnums=(0, 1)))(x, w)
        out.append(_close("fused_xent_fwd", [n, d_model, vocab],
                          got[0], want[0], rtol=1e-5, atol=0))
        out.append(_close("fused_xent_bwd", [n, d_model, vocab],
                          got[1], want[1], rtol=5e-4, atol=1e-6))

        # paged attention, dense KV, one decode step: GPT-2 xl's 25
        # heads of 64 (a pool row of 1,600 lanes in 1,664) and 16 of 128
        # (whole tiles), slots of every length from idle to the whole
        # table, dead table entries at the trash block
        bs, nblocks = 16, 1 + paged_slots * paged_width
        rs = np.random.RandomState(SEED)
        held = rs.randint(0, paged_width * bs + 1, (paged_slots,))
        edge = [0, 1, paged_width * bs][:paged_slots]
        held[:len(edge)] = edge
        tables = rs.randint(1, nblocks, (paged_slots, paged_width))
        tables[np.arange(paged_width)[None, :]
               >= -(-held // bs)[:, None]] = 0
        tables = jnp.asarray(tables, jnp.int32)
        q_pos = jnp.asarray(held[:, None] - 1, jnp.int32)
        for n, (p_heads, p_dim) in enumerate(paged_shapes):
            cache = (nblocks * bs, p_heads, p_dim)
            ck = pool_rows(jax.random.normal(key[6], cache, jnp.float32))
            cv = pool_rows(jax.random.normal(key[7], cache, jnp.float32))
            pq = jax.random.normal(key[n], (paged_slots, 1, p_heads, p_dim),
                                   jnp.float32)
            info = {"block_size": bs, "table_width": paged_width,
                    "q_len": 1, "num_heads": p_heads, "head_dim": p_dim,
                    "kv_mode": "dense", "kv_itemsize": 4}
            chosen = registry.resolve_impl("paged_attention", info=info)
            if on_chip and chosen != "pallas":
                raise RuntimeError(
                    f"auto resolved paged attention at {p_heads} heads of "
                    f"{p_dim} to {chosen!r} on this chip")
            got = jax.jit(lambda *a: registry.dispatch(
                "paged_attention", *a, info=info, kv_mode="dense",
                block_size=bs))(pq, ck, cv, tables, q_pos)
            want = jax.jit(lambda *a: paged_attention_reference(
                *a, kv_mode="dense", block_size=bs))(pq, ck, cv, tables,
                                                     q_pos)
            live = held > 0    # an idle slot's output is discarded
            out.append(_close(f"paged_attention_dense_H{p_heads}_Dh{p_dim}",
                              [paged_slots, paged_width * bs, p_heads,
                               p_dim], got[live], want[live],
                              rtol=0, atol=2e-6))

    # EVA attention, one decode step at the longdoc cell's shape: bf16
    # rows, 32 heads of 128, a table of 128 window + 64 summary blocks;
    # slots from a first byte to the last position the table holds, one
    # idle; entries outside a slot's live runs at the trash block.  At
    # the default precision, as the cell runs it: under "highest" Mosaic
    # refuses the kernel's bf16 products ("Bad lhs type")
    e_heads, e_dim = eva_heads
    bs, wb = eva_chunk, eva_window // eva_chunk
    width = wb + eva_summary_blocks
    slots = len(eva_positions)
    nblocks = 1 + slots * width
    ids = rs.permutation(np.arange(1, nblocks)).reshape(slots, width)
    pos = np.asarray(eva_positions)
    n_win, n_sum = live_blocks(np.maximum(pos, 0), eva_window,
                               eva_chunk, bs)
    entry = np.arange(width)[None, :]
    live = (entry < n_win[:, None]) | (
        (entry >= wb) & (entry < wb + n_sum[:, None]))
    tables = jnp.asarray(np.where(live & (pos >= 0)[:, None], ids, 0),
                         jnp.int32)
    rows = (nblocks * bs, e_heads * e_dim)
    ck = jax.random.normal(key[6], rows, jnp.bfloat16)
    cv = jax.random.normal(key[7], rows, jnp.bfloat16)
    eq = jax.random.normal(key[0], (slots, 1, e_heads, e_dim),
                           jnp.bfloat16)
    q_pos = jnp.asarray(pos[:, None], jnp.int32)
    info = {"block_size": bs, "table_width": width, "q_len": 1,
            "num_heads": e_heads, "head_dim": e_dim, "kv_mode": "dense",
            "kv_itemsize": 2, "window": eva_window, "chunk": eva_chunk}
    chosen = registry.resolve_impl("eva_attention", info=info)
    if on_chip and chosen != "pallas":
        raise RuntimeError(
            f"auto resolved EVA attention at {e_heads} heads of "
            f"{e_dim} to {chosen!r} on this chip")
    kw = dict(window=eva_window, chunk=eva_chunk, block_size=bs)
    got = jax.jit(lambda *a: registry.dispatch(
        "eva_attention", *a, info=info, **kw))(eq, ck, cv, tables, q_pos)
    want = jax.jit(lambda *a: eva_attention_reference(*a, **kw))(
        eq, ck, cv, tables, q_pos)
    # bf16 operands and probabilities on both sides, float32 sums
    # in another order
    out.append(_close(f"eva_attention_bf16_H{e_heads}_Dh{e_dim}",
                      [slots, width * bs, e_heads, e_dim],
                      got[pos >= 0], want[pos >= 0], rtol=0, atol=1e-2))

    # attention over grouped rows, one decode step of a full layer at
    # the chatrate cell's shape (64 slots, 32 query heads on 8 K/V heads
    # of 64, a table of 128 blocks, scores times 1/64) and at mixedlen's
    # (16 slots, 128 on 8 of 128, 1,024 blocks): bf16 rows, slots from
    # idle to the whole table, dead entries at the trash block.  At the
    # default precision too
    from deepspeed_tpu.serving.layers import grouped_attention_reference

    def held_tables(slots, width, bs=16):
        """Slots from idle to the whole table, dead entries at the trash
        block: -> (rows held [slots], tables, q_pos, blocks in the pool)."""
        nblocks = 1 + slots * width
        held = rs.randint(0, width * bs + 1, (slots,))
        edge = [0, 1, width * bs, bs, bs + 1][:slots]
        held[:len(edge)] = edge
        tables = rs.permutation(np.arange(1, nblocks)).reshape(slots, width)
        tables[np.arange(width)[None, :] >= -(-held // bs)[:, None]] = 0
        return (held, jnp.asarray(tables, jnp.int32),
                jnp.asarray(held[:, None] - 1, jnp.int32), nblocks)

    for slots, g_heads, g_kv, g_dim, width, scale in grouped_shapes:
        bs = 16
        held, tables, q_pos, nblocks = held_tables(slots, width)
        rows = (nblocks * bs, g_kv, g_dim)
        ck = pool_rows(jax.random.normal(key[6], rows, jnp.bfloat16))
        cv = pool_rows(jax.random.normal(key[7], rows, jnp.bfloat16))
        gq = jax.random.normal(key[0], (slots, 1, g_heads, g_dim),
                               jnp.bfloat16)
        info = {"block_size": bs, "table_width": width, "q_len": 1,
                "num_heads": g_heads, "kv_heads": g_kv, "head_dim": g_dim,
                "kv_mode": "dense", "kv_itemsize": 2, "window": 0,
                "ring": False}
        chosen = registry.resolve_impl("grouped_attention", info=info)
        if on_chip and chosen != "pallas":
            raise RuntimeError(
                f"auto resolved grouped attention at {g_heads} heads on "
                f"{g_kv} of {g_dim} to {chosen!r} on this chip")
        kw = dict(kv_heads=g_kv, block_size=bs, scale=scale)
        got = jax.jit(lambda *a: registry.dispatch(
            "grouped_attention", *a, info=info, **kw))(gq, ck, cv, tables,
                                                       q_pos)
        want = jax.jit(lambda *a: grouped_attention_reference(*a, **kw))(
            gq, ck, cv, tables, q_pos)
        out.append(_close(
            f"grouped_attention_bf16_H{g_heads}_KV{g_kv}_Dh{g_dim}",
            [slots, width * bs, g_heads, g_dim], got[held > 0],
            want[held > 0], rtol=0, atol=1e-2))

    # a sliding layer's decode step at mixedlen's tile (128 query heads
    # on 8 K/V heads of 128, a ring of 288 blocks under a window of
    # 4,096): slots several laps in, short of the window, on both sides
    # of the window's and the ring's ends, one idle; the walk of the
    # window's blocks modulo the ring against the gather of every ring
    # under `newest`.  bf16 rows, the default precision
    s_heads, s_kv, s_dim, ring, window = sliding_shape
    bs, pos = 16, np.asarray(sliding_positions)
    slots = len(pos)
    nblocks = 1 + slots * ring
    tables = jnp.asarray(rs.permutation(np.arange(1, nblocks)).reshape(
        slots, ring), jnp.int32)
    rows = (nblocks * bs, s_kv, s_dim)
    ck = pool_rows(jax.random.normal(key[6], rows, jnp.bfloat16))
    cv = pool_rows(jax.random.normal(key[7], rows, jnp.bfloat16))
    sq = jax.random.normal(key[0], (slots, 1, s_heads, s_dim), jnp.bfloat16)
    info = {"block_size": bs, "table_width": ring, "q_len": 1,
            "num_heads": s_heads, "kv_heads": s_kv, "head_dim": s_dim,
            "kv_mode": "dense", "kv_itemsize": 2, "window": window,
            "ring": True}
    chosen = registry.resolve_impl("grouped_attention", info=info)
    if on_chip and chosen != "pallas":
        raise RuntimeError(
            f"auto resolved a sliding layer's decode step at {s_heads} "
            f"heads on {s_kv} of {s_dim}, a ring of {ring} blocks under a "
            f"window of {window}, to {chosen!r} on this chip")
    kw = dict(kv_heads=s_kv, block_size=bs, scale=None, window=window)
    a = (sq, ck, cv, tables, jnp.asarray(pos[:, None], jnp.int32),
         jnp.asarray(np.maximum(pos, 0), jnp.int32))
    got = jax.jit(lambda q, k, v, tbl, q_pos, newest: registry.dispatch(
        "grouped_attention", q, k, v, tbl, q_pos, info=info, newest=newest,
        **kw))(*a)
    want = jax.jit(lambda q, k, v, tbl, q_pos, newest:
                   grouped_attention_reference(q, k, v, tbl, q_pos,
                                               newest=newest, **kw))(*a)
    out.append(_close(
        f"sliding_attention_bf16_H{s_heads}_KV{s_kv}_Dh{s_dim}_ring{ring}",
        [slots, ring * bs, s_heads, s_dim], got[pos >= 0], want[pos >= 0],
        rtol=0, atol=1e-2))

    # and a prefill chunk of mixedlen's full layer (one request, 512
    # queries of 128 heads on 8 K/V heads of 128, a table of 1,024
    # blocks): a first chunk, one whose run ends inside a block, and a
    # last chunk whose padded tail runs past the table — its valid rows
    # agree; entries behind the run at the trash block.  bf16 rows, the
    # default precision
    p_heads, p_kv, p_dim, width, chunk = prefill_shape
    bs, nblocks = 16, 1 + width
    rows = (nblocks * bs, p_kv, p_dim)
    ck = pool_rows(jax.random.normal(key[6], rows, jnp.bfloat16))
    cv = pool_rows(jax.random.normal(key[7], rows, jnp.bfloat16))
    pq = jax.random.normal(key[0], (1, chunk, p_heads, p_dim), jnp.bfloat16)
    info = {"block_size": bs, "table_width": width, "q_len": chunk,
            "num_heads": p_heads, "kv_heads": p_kv, "head_dim": p_dim,
            "kv_mode": "dense", "kv_itemsize": 2, "window": 0,
            "ring": False, "batch": 1}
    chosen = registry.resolve_impl("grouped_attention", info=info)
    if on_chip and chosen != "pallas":
        raise RuntimeError(
            f"auto resolved a prefill chunk of {chunk} queries at {p_heads} "
            f"heads on {p_kv} of {p_dim} to {chosen!r} on this chip")
    kw = dict(kv_heads=p_kv, block_size=bs, scale=None)
    walk = jax.jit(lambda *a: registry.dispatch(
        "grouped_attention", *a, info=info, **kw))
    gather = jax.jit(lambda *a: grouped_attention_reference(*a, **kw))
    entries = rs.permutation(np.arange(1, nblocks))
    for start in prefill_starts:
        valid = min(chunk, width * bs - start)
        table = np.where(np.arange(width) < -(-(start + valid) // bs),
                         entries, 0)
        a = (pq, ck, cv, jnp.asarray(table[None], jnp.int32),
             jnp.asarray(start + np.arange(chunk)[None], jnp.int32))
        out.append(_close(
            f"grouped_prefill_bf16_H{p_heads}_KV{p_kv}_Dh{p_dim}_at{start}",
            [1, chunk, width * bs, p_heads, p_dim], walk(*a)[:, :valid],
            gather(*a)[:, :valid], rtol=0, atol=1e-2))

    # and a prefill chunk of mixedlen's sliding layers (one request, 512
    # queries, a ring of 288 blocks = window + chunk under a window of
    # 4,096): a first chunk, the one that fills the window, the one that
    # fills the ring, the first past the wrap, one off a block's edge
    # and one several laps in; each tile of 64 queries walks the blocks
    # from its oldest lower bound modulo the ring, against the gather of
    # the whole ring under `newest`.  bf16 rows, the default precision
    c_heads, c_kv, c_dim, ring, window, chunk = sliding_chunk_shape
    bs, nblocks = 16, 1 + ring
    rows = (nblocks * bs, c_kv, c_dim)
    ck = pool_rows(jax.random.normal(key[6], rows, jnp.bfloat16))
    cv = pool_rows(jax.random.normal(key[7], rows, jnp.bfloat16))
    cq = jax.random.normal(key[0], (1, chunk, c_heads, c_dim), jnp.bfloat16)
    info = {"block_size": bs, "table_width": ring, "q_len": chunk,
            "num_heads": c_heads, "kv_heads": c_kv, "head_dim": c_dim,
            "kv_mode": "dense", "kv_itemsize": 2, "window": window,
            "ring": True, "batch": 1}
    chosen = registry.resolve_impl("grouped_attention", info=info)
    if on_chip and chosen != "pallas":
        raise RuntimeError(
            f"auto resolved a sliding layer's prefill chunk of {chunk} "
            f"queries at {c_heads} heads on {c_kv} of {c_dim}, a ring of "
            f"{ring} blocks under a window of {window}, to {chosen!r} on "
            f"this chip")
    kw = dict(kv_heads=c_kv, block_size=bs, scale=None, window=window)
    walk = jax.jit(lambda q, k, v, tbl, q_pos: registry.dispatch(
        "grouped_attention", q, k, v, tbl, q_pos, info=info,
        newest=q_pos[:, -1], **kw))
    gather = jax.jit(lambda q, k, v, tbl, q_pos: grouped_attention_reference(
        q, k, v, tbl, q_pos, newest=q_pos[:, -1], **kw))
    table = jnp.asarray(rs.permutation(np.arange(1, nblocks))[None],
                        jnp.int32)
    for start in sliding_chunk_starts:
        a = (cq, ck, cv, table,
             jnp.asarray(start + np.arange(chunk)[None], jnp.int32))
        out.append(_close(
            f"sliding_prefill_bf16_H{c_heads}_KV{c_kv}_Dh{c_dim}_ring{ring}"
            f"_at{start}", [1, chunk, ring * bs, c_heads, c_dim], walk(*a),
            gather(*a), rtol=0, atol=1e-2))

    # attention over latent rows, one decode step at the chatgen cell's
    # shape (32 slots, 16 heads' absorbed queries over rows of 512 + 64
    # values in 640 lanes, a table of 256 blocks): one array that is key
    # and value, bf16, the same slots and the default precision
    from deepspeed_tpu.serving.layers import latent_attention_reference

    slots, l_heads, rank, rope, width = latent_shape
    bs = 16
    held, tables, q_pos, nblocks = held_tables(slots, width)
    pool = pool_rows(jax.random.normal(
        key[6], (nblocks * bs, 1, rank + rope), jnp.bfloat16))
    lq = jax.random.normal(key[0], (slots, 1, l_heads, rank + rope),
                           jnp.bfloat16)
    info = {"block_size": bs, "table_width": width, "q_len": 1,
            "num_heads": l_heads, "kv_heads": 1, "head_dim": rank + rope,
            "kv_mode": "dense", "kv_itemsize": 2}
    chosen = registry.resolve_impl("latent_attention", info=info)
    if on_chip and chosen != "pallas":
        raise RuntimeError(
            f"auto resolved latent attention at {l_heads} heads over rows "
            f"of {rank} + {rope} to {chosen!r} on this chip")
    kw = dict(block_size=bs, rank=rank, scale=(rank + rope) ** -0.5)
    got = jax.jit(lambda *a: registry.dispatch(
        "latent_attention", *a, info=info, **kw))(lq, pool, tables, q_pos)
    want = jax.jit(lambda *a: latent_attention_reference(*a, **kw))(
        lq, pool, tables, q_pos)
    out.append(_close(
        f"latent_attention_bf16_H{l_heads}_W{rank + rope}",
        [slots, width * bs, l_heads, rank + rope], got[held > 0],
        want[held > 0], rtol=0, atol=1e-2))

    # the routed product of a decode step at the chatgen cell's shape:
    # 32 slots of which 12 are live, 6 of 64 bf16 experts a slot, the
    # dead slots' rows left as they are.  At the default precision too
    # ... and at the reasoning cell's: 40 slots of which 2 are live,
    # two matrices an expert of the whole width 1,856 (no whole-tile
    # share divides it)
    from deepspeed_tpu.moe import dropless

    for (T, E, D, F), live_rows, gated in ((routed_shape, routed_live, True),
                                           (relu2_shape, relu2_live, False)):
        mk = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                               * D ** -0.5).astype(jnp.bfloat16)
        experts = {"up": mk(key[2], (E, D, F)), "down": mk(key[3], (E, F, D))}
        if gated:
            experts["gate"] = mk(key[1], (E, D, F))
        x = jax.random.normal(key[4], (T, D), jnp.float32)
        weights, idx = dropless.route(
            x, jax.random.normal(key[5], (D, E), jnp.float32) * D ** -0.5, 6)
        alive = jnp.arange(T) < live_rows
        way = dropless.routed_way(T, 6, experts)
        if on_chip and way != "touched":
            raise RuntimeError(
                f"a call of {T} rows over {E} experts of {D} x {F} takes the "
                f"{way} way on this chip")
        got = jax.jit(dropless.experts_touched_only)(x, experts, weights, idx,
                                                     alive)
        want = jax.jit(dropless.experts_masked)(
            x, experts, jnp.where(alive[:, None], weights, 0.0), idx)
        # the kernel's weighted sum is float32 on the VPU; where XLA makes
        # the oracle's an MXU product it enters as bf16 at this precision
        out.append(_close(
            f"touched_experts_bf16_T{T}_E{E}" + ("" if gated else "_relu2"),
            [T, E, D, F], got, want, rtol=0,
            atol=1e-2 * float(jnp.abs(want).max())))
        if int(dropless.experts_touched(idx, alive, E)) in (0, E) or \
                np.asarray(got)[live_rows:].any():
            raise RuntimeError(
                "the routed check's dead slots touched experts")

    # the routed product of a prefill chunk at the longchat and mixedlen
    # cells' shapes: 512 tokens, the last 100 a padded tail that is not
    # live, an eighth of the experts held; the held rows walked a slab at
    # a time against XLA's grouped products over every assignment; and
    # at the reasoning cell's, two matrices an expert
    for (T, top_k, E, total, D, F), gated in (
            *((shape, True) for shape in slab_shapes), (relu2_slab, False)):
        mk = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                               * D ** -0.5).astype(jnp.bfloat16)
        experts = {"up": mk(key[2], (E, D, F)), "down": mk(key[3], (E, F, D))}
        if gated:
            experts["gate"] = mk(key[1], (E, D, F))
        x = jax.random.normal(key[4], (T, D), jnp.float32)
        weights, idx, held = dropless.held_assignments(*dropless.route(
            x, jax.random.normal(key[5], (D, total), jnp.float32)
            * D ** -0.5, top_k, renormalize=True), total // 4, E)
        alive = jnp.arange(T) < T - 100
        way = dropless.routed_way(T, top_k, experts, total)
        if on_chip and way != "slabs":
            raise RuntimeError(
                f"a chunk of {T} rows over {E} of {total} experts of {D} x "
                f"{F} takes the {way} way on this chip")
        got = jax.jit(lambda *a: dropless.experts_slabs(
            *a, total, held, alive))(x, experts, weights, idx)
        want = jax.jit(dropless.experts_grouped)(x, experts, weights, idx,
                                                 held)
        out.append(_close(
            f"grouped_experts_bf16_T{T}_E{E}of{total}"
            + ("" if gated else "_relu2"), [T, E, D, F],
            got[alive], want[alive], rtol=0,
            atol=1e-2 * float(jnp.abs(want).max())))
        if np.asarray(got)[T - 100:].any() or not bool(held.any()):
            raise RuntimeError("the slab check's padded tail was multiplied")

    # the state-space recurrence of a decode step at the chatrate cell's
    # shape: 64 slots of which 37 run, scattered; the others' state
    # comes back bit for bit and their y is zeros
    from deepspeed_tpu.kernels.ssm import live_slots, ssm_step_info
    from deepspeed_tpu.models.granite_hybrid import ssm_step

    from deepspeed_tpu.models.granite_hybrid import by_group

    # ... and at the reasoning cell's: 40 slots of which 24 run, a B and
    # a C for each of 8 groups of 8 heads
    for (B, H, P, N, G), n_live in (((*ssm_shape, 1), ssm_live),
                                    (ssm_groups_shape, ssm_groups_live)):
        runs = jnp.zeros((B,), bool).at[
            jax.random.permutation(key[6], B)[:n_live]].set(True)
        sx, sB, sC = (jax.random.normal(k, shape, jnp.float32)
                      for k, shape in zip(key[:3], (
                          (B, H, P), *[(B, G, N) if G > 1 else (B, N)] * 2)))
        dt = jax.random.uniform(key[3], (B, H), minval=0.001,
                                maxval=0.1) * runs[:, None]
        A = -jax.random.uniform(key[4], (H,), minval=1.0, maxval=16.0)
        state = jax.random.normal(key[5], (B, H, P, N), jnp.float32)
        info = ssm_step_info(state, G)
        chosen = registry.resolve_impl("ssm_step", info=info)
        if on_chip and chosen != "pallas":
            raise RuntimeError(
                f"auto resolved the recurrence over a state of "
                f"{(B, H, P, N)} in {G} group(s) to {chosen!r} on this chip")
        got = jax.jit(lambda *a: registry.dispatch("ssm_step", *a, info=info))(
            sx, sB, sC, dt, A, state, *live_slots(runs))
        want = jax.jit(ssm_step if G == 1 else by_group(ssm_step, G))(
            sx, sB, sC, dt, A, state)
        out.append(_close(f"ssm_step_B{B}_H{H}_P{P}_N{N}_live{n_live}"
                          + (f"_G{G}" if G > 1 else ""),
                          [B, H, P, N], jax.tree_util.tree_map(
                              lambda a: a[runs], got),
                          jax.tree_util.tree_map(lambda a: a[runs], want),
                          rtol=1e-5, atol=1e-4))
        rest = ~np.asarray(runs)
        if chosen == "pallas" and (np.asarray(got[0])[rest].any() or not
                                   np.array_equal(np.asarray(got[1])[rest],
                                                  np.asarray(state)[rest])):
            raise RuntimeError(
                "the recurrence touched a slot that does not run")

    # the gated delta rule of a decode step at the longchat cell's shape:
    # 48 slots of which 29 run, scattered; the others' state comes back
    # bit for bit and their output is zeros
    from deepspeed_tpu.kernels.gdn import gdn_step_info
    from deepspeed_tpu.models.qwen3_next import delta_step

    B, H, d = gdn_shape
    runs = jnp.zeros((B,), bool).at[
        jax.random.permutation(key[6], B)[:gdn_live]].set(True)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    gq, gk, gv = (jax.random.normal(k, (B, H, d), jnp.float32)
                  for k in key[:3])
    gq, gk = unit(gq) * d ** -0.5, unit(gk)
    g = -jax.random.uniform(key[3], (B, H), maxval=1.0) * runs[:, None]
    beta = jax.random.uniform(key[4], (B, H)) * runs[:, None]
    state = jax.random.normal(key[5], (B, H, d, d), jnp.float32)
    info = gdn_step_info(state)
    chosen = registry.resolve_impl("gdn_step", info=info)
    if on_chip and chosen != "pallas":
        raise RuntimeError(f"auto resolved the delta rule over a state of "
                           f"{gdn_shape} to {chosen!r} on this chip")
    got = jax.jit(lambda *a: registry.dispatch("gdn_step", *a, info=info))(
        gq, gk, gv, g, beta, state, *live_slots(runs))
    want = jax.jit(delta_step)(gq, gk, gv, g, beta, state)
    out.append(_close(f"gdn_step_B{B}_H{H}_D{d}_live{gdn_live}",
                      list(gdn_shape), jax.tree_util.tree_map(
                          lambda a: a[runs], got),
                      jax.tree_util.tree_map(lambda a: a[runs], want),
                      rtol=1e-5, atol=1e-4))
    rest = ~np.asarray(runs)
    if chosen == "pallas" and (np.asarray(got[0])[rest].any() or not
                               np.array_equal(np.asarray(got[1])[rest],
                                              np.asarray(state)[rest])):
        raise RuntimeError("the delta rule touched a slot that does not run")

    # a prefill chunk's attention over a learned selection at the longctx
    # cell's shape: one request's 512 queries from position 4,608 on (five
    # tiles of 1,024 of a table that holds 24), each over the 2,048 rows
    # `select_mask` chooses among those it sees; the table's entries behind
    # the tiles walked are the trash block, which holds NaN
    import types

    from deepspeed_tpu.models.glm_moe_dsa import select_mask
    from deepspeed_tpu.serving import sparse

    T, H, nope, rope, v, rank = masked_shape
    bs, width, tile, topk = masked_table
    cfg = types.SimpleNamespace(kv_lora_rank=rank, v_head_dim=v,
                                qk_rope_head_dim=rope, head_dim=nope + rope,
                                yarn=None)
    sched = types.SimpleNamespace(block_size=bs)
    q_pos = masked_start + jnp.arange(T)
    n_tiles = sparse._tiles_needed(q_pos, tile, width * bs // tile)
    at = jnp.arange(width)
    tables = jnp.where(at < n_tiles * (tile // bs), jax.random.permutation(
        key[0], width) + 1, 0)[None].astype(jnp.int32)
    lanes = -(-(rank + rope) // 128) * 128
    pool = jax.random.normal(key[1], ((width + 1) * bs, lanes),
                             jnp.bfloat16).at[:bs].set(jnp.nan)
    kv_b = (jax.random.normal(key[2], (rank, H * (nope + v)), jnp.float32)
            * rank ** -0.5).astype(jnp.bfloat16)
    q_nope = jax.random.normal(key[3], (1, T, H, nope), jnp.bfloat16)
    q_rope = jax.random.normal(key[4], (1, T, H, rope), jnp.bfloat16)
    mask = select_mask(
        jax.random.normal(key[5], (1, T, width * bs), jnp.float32),
        jnp.arange(width * bs)[None, None, :] <= q_pos[None, :, None], topk)
    info = sparse.masked_info(cfg, q_nope, q_rope, pool, kv_b, tile)
    chosen = registry.resolve_impl("masked_latent_attention", info=info)
    if on_chip and chosen != "pallas":
        raise RuntimeError(
            f"auto resolved a chunk's attention over its selection at "
            f"{masked_shape} to {chosen!r} on this chip")
    operands = (kv_b, q_nope, q_rope, pool, tables, mask, n_tiles)
    got = jax.jit(lambda *a: registry.dispatch(
        "masked_latent_attention", cfg, *a, sched, tile, info=info))(
        *operands)
    want = jax.jit(lambda *a: sparse.attend_tiles(cfg, *a, sched, tile))(
        *operands)
    out.append(_close(
        f"masked_latent_attention_bf16_T{T}_H{H}_tiles{int(n_tiles)}",
        [T, H, nope + rope, v, int(n_tiles) * tile], got, want, rtol=0,
        atol=2e-3 * float(jnp.abs(want).max())))
    if int(mask.sum(-1).min()) != min(topk, masked_start + 1):
        raise RuntimeError("the selection check's queries chose other than "
                           "`topk` rows")

    # the gated short convolution at the assist cell's shape (PR 63; no
    # kernel: `jax.numpy` at the chip's default precision against float32
    # at the highest): a decode step of 96 slots of which 64 run,
    # scattered, whose rows move on by one while the others' come back
    # to the bit; and a prefill chunk of 512 of which the last 100 are a
    # padded tail, which leaves its last two VALID inputs
    from deepspeed_tpu.models.lfm2_moe import conv_mix

    B, D, K, chunk = conv_shape
    cspec = types.SimpleNamespace(conv_taps=K)
    mk = lambda k, shape, scale: (jax.random.normal(
        k, shape, jnp.float32) * scale).astype(jnp.bfloat16)
    cp = {"in": mk(key[1], (D, 3 * D), D ** -0.5),
          "conv_w": mk(key[2], (D, K), K ** -0.5),
          "out": mk(key[3], (D, D), D ** -0.5)}

    def conv_by_hand(h, rows, n_valid):
        with jax.default_matmul_precision("highest"):
            f32 = lambda a: a.astype(jnp.float32)
            # (the layer's products are at the weights' dtype)
            b, c, u = jnp.split(f32(h.astype(jnp.bfloat16)) @ f32(cp["in"]),
                                3, axis=-1)
            seq = jnp.concatenate(
                [f32(rows), f32((b * u).astype(rows.dtype))], axis=1)
            conv = sum(seq[:, j:j + h.shape[1]] * f32(cp["conv_w"])[:, j]
                       for j in range(K))
            kept = jnp.stack([jax.lax.dynamic_slice_in_dim(
                seq[i], n_valid[i], K - 1) for i in range(h.shape[0])])
            return (c * conv) @ f32(cp["out"]), kept.astype(rows.dtype)

    mix = jax.jit(lambda h, rows, n: conv_mix(cspec, cp, h, rows, n))
    for label, h, rows, n_valid in (
            (f"step_B{B}_live{conv_live}",
             jax.random.normal(key[4], (B, 1, D), jnp.float32),
             mk(key[5], (B, K - 1, D), 1.0),
             jnp.zeros((B,), jnp.int32).at[jax.random.permutation(
                 key[6], B)[:conv_live]].set(1)),
            (f"chunk_T{chunk}_valid{chunk - 100}",
             jax.random.normal(key[4], (1, chunk, D), jnp.float32),
             mk(key[5], (1, K - 1, D), 1.0),
             jnp.full((1,), chunk - 100, jnp.int32))):
        got, want = mix(h, rows, n_valid), conv_by_hand(h, rows, n_valid)
        real = np.arange(h.shape[1])[None, :] < np.asarray(n_valid)[:, None]
        out.append(_close(
            f"conv_mix_bf16_D{D}_{label}", [h.shape[0], h.shape[1], D, K],
            (got[0][real], got[1].astype(jnp.float32)),
            (want[0][real], want[1].astype(jnp.float32)), rtol=0,
            atol=2e-2 * float(jnp.abs(want[0]).max())))
        rest = np.asarray(n_valid) == 0
        if not np.array_equal(np.asarray(got[1])[rest],
                              np.asarray(rows)[rest]):
            raise RuntimeError(
                "the convolution moved the rows of a slot that does not run")

    # ... and the routed products at its shape, behind a share: 96 slots
    # of which 64 are live choose 4 of 64 experts and the 8 held are ALL
    # touched, each multiplying a few rows (the regime no other cell
    # has); a chunk of 512 over slabs of the rows held
    T, top_k, E, total, D, F = held_shape
    experts = {name: mk(k, shape, D ** -0.5) for name, k, shape in (
        ("gate", key[1], (E, D, F)), ("up", key[2], (E, D, F)),
        ("down", key[3], (E, F, D)))}
    x = jax.random.normal(key[4], (T, D), jnp.float32)
    weights, idx, held = dropless.held_assignments(*dropless.route(
        x, jax.random.normal(key[5], (D, total), jnp.float32) * D ** -0.5,
        top_k, scoring="sigmoid", renormalize=True, renorm_eps=1e-6), 0, E)
    alive = jnp.arange(T) < held_live
    way = dropless.routed_way(T, top_k, experts, total)
    if on_chip and way != "touched":
        raise RuntimeError(
            f"a call of {T} rows over {E} of {total} experts of {D} x {F} "
            f"takes the {way} way on this chip")
    got = jax.jit(dropless.experts_touched_only)(x, experts, weights, idx,
                                                 alive, held)
    want = jax.jit(dropless.experts_masked)(
        x, experts, jnp.where(alive[:, None], weights, 0.0), idx)
    out.append(_close(
        f"touched_experts_bf16_T{T}_E{E}of{total}", [T, E, D, F], got, want,
        rtol=0, atol=1e-2 * float(jnp.abs(want).max())))
    touched = int(dropless.experts_touched(idx, alive, E, held))
    if (on_chip and touched != E) or np.asarray(got)[held_live:].any():
        raise RuntimeError(
            f"the share's check touched {touched} of {E} held experts, or "
            f"its dead slots touched some")
    T, top_k, E, total, D, F = held_slab
    x = jax.random.normal(key[4], (T, D), jnp.float32)
    weights, idx, held = dropless.held_assignments(*dropless.route(
        x, jax.random.normal(key[5], (D, total), jnp.float32) * D ** -0.5,
        top_k, scoring="sigmoid", renormalize=True), 0, E)
    alive = jnp.arange(T) < T - 100
    way = dropless.routed_way(T, top_k, experts, total)
    if on_chip and way != "slabs":
        raise RuntimeError(
            f"a chunk of {T} rows over {E} of {total} experts of {D} x {F} "
            f"takes the {way} way on this chip")
    got = jax.jit(lambda *a: dropless.experts_slabs(*a, total, held, alive))(
        x, experts, weights, idx)
    want = jax.jit(dropless.experts_grouped)(x, experts, weights, idx, held)
    out.append(_close(
        f"grouped_experts_bf16_T{T}_E{E}of{total}_sigmoid", [T, E, D, F],
        got[alive], want[alive], rtol=0,
        atol=1e-2 * float(jnp.abs(want).max())))

    # not a failure but an answer: does this chip's compiler keep
    # `_own_lanes`' two slices right (16 rows of 2 K/V heads of 256)?
    # Once it reads true here, `_own_lanes_of_two` may go
    from deepspeed_tpu.kernels import paged

    raw = jax.random.normal(key[0], (8, 16, 512), jnp.float32)
    slices, masked = (np.asarray(jax.jit(
        lambda o, keep=keep: keep(o, 1, 16, 8, 256))(raw))
        for keep in (paged._own_lanes, paged._own_lanes_of_two))
    return {"phase": "kernels", "native": not pallas_backend.interpret(),
            "kernels": out,
            "own_lanes_two_slices_right": bool(
                np.array_equal(slices, masked))}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chip_phase(size=MODEL_SIZE, depth=TRAIN_DEPTH, seq=SEQ,
                    micro=1, steps=TRAIN_STEPS, n_dev=4, on_chip=True,
                    **over) -> dict:
    """The train phase's model under ZeRO-2 over mesh {"data": n_dev},
    against the same steps on the same global batch on ONE device of
    the same process."""
    import jax

    from deepspeed_tpu.comm import make_mesh

    devices = jax.devices()[:n_dev]
    global_batch = micro * n_dev
    cfg1 = gpt_config(depth, seq, 1, size, **over)
    batch = make_batch(cfg1, global_batch, seq)

    ref = build_engine(cfg1, global_batch, make_mesh(devices=devices[:1]))
    ref_losses, ref_setup, _ = run_steps(ref, batch, steps)
    del ref
    gc.collect()  # device 0 is about to hold its quarter of the next engine

    engine = build_engine(gpt_config(depth, seq, n_dev, size, **over), micro,
                          make_mesh(devices=devices))
    text = lowered_step(engine, batch).compile().as_text()
    # the gradient reduction: a reduce-scatter or all-reduce whose one
    # replica group is all n_dev devices ({{0,1,2,3}} or [1,4]<=[4])
    whole = (f"{{{{{','.join(map(str, range(n_dev)))}}}}}",
             f"[1,{n_dev}]<=[{n_dev}]")
    collectives = sorted({
        m.group(1) for m in re.finditer(
            r" (reduce-scatter|all-reduce|all-gather)(?:-start)?\("
            r".*?replica_groups=(\{\{[\d,]*\}\}|\[[\d,]*\]<=\[\d+\])", text)
        if m.group(2) in whole})
    if not {"reduce-scatter", "all-reduce"} & set(collectives):
        raise RuntimeError(
            f"no reduce-scatter or all-reduce over {n_dev} devices in the "
            f"step's HLO (collectives over {n_dev}: {collectives})")
    losses, setup_s, run_s = run_steps(engine, batch, steps)
    check_losses(losses)
    np.testing.assert_allclose(losses[:1], ref_losses[:1], **ZERO_PARITY)
    np.testing.assert_allclose(losses, ref_losses, **BF16_DRIFT)
    rel = np.abs(np.array(losses) / np.array(ref_losses) - 1)

    def shard_devices(tree):
        leaf = max(jax.tree_util.tree_leaves(tree), key=lambda a: a.size)
        return sorted({s.device.id for s in leaf.addressable_shards
                       if s.data.size < leaf.size})

    opt_devs = shard_devices(engine._opt_state)
    if engine._grad_acc is None:
        engine._grad_acc = engine._zero_grad_acc()
    acc_devs = shard_devices(engine._grad_acc)
    if len(opt_devs) != n_dev or len(acc_devs) != n_dev:
        raise RuntimeError(
            f"ZeRO-2 state is not sharded over {n_dev} devices: optimizer "
            f"{opt_devs}, gradient accumulator {acc_devs}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if on_chip and not all(in_use):
        raise RuntimeError(f"a device reports no bytes in use: {in_use}")
    return {"phase": "four_chip", "mesh": {"data": n_dev}, "depth": depth,
            "d_model": cfg1.d_model, "seq": seq,
            "global_batch": global_batch, "losses": losses,
            "one_device_losses": ref_losses,
            "relative_difference": [float(f"{r:.2e}") for r in rel],
            "steps_within_zero_parity": int(np.sum(np.isclose(
                losses, ref_losses, **ZERO_PARITY))),
            "first_step_parity": ZERO_PARITY,
            "first_step_parity_from": "tests/test_engine.py::"
                                      "test_zero_stages_converge_identically",
            "later_steps_bound": BF16_DRIFT,
            "collectives_in_step": collectives,
            "optimizer_state_devices": opt_devs,
            "grad_accumulator_devices": acc_devs,
            "bytes_in_use": in_use,
            "setup_seconds_smoke": round(setup_s + ref_setup, 2),
            "step_seconds_smoke": round(run_s / steps, 4)}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    phases = ([four_chip_phase] if args.chips == 4
              else [train_phase, serve_phase, evabyte_phase, kernels_phase])
    name = "device"
    t0 = time.perf_counter()
    cached_at_start = cache_entries(cache_dir)
    try:
        device = device_phase(args.chips, cache_dir)
        emit(device)
        for phase in phases:
            name = phase.__name__
            emit(phase())
            gc.collect()  # the next phase starts from a clean HBM
    except Exception as e:
        traceback.print_exc()
        emit({"ok": False, "phase": name, "error": f"{type(e).__name__}: {e}"})
        return 1
    cached = cache_entries(cache_dir)
    emit({"phase": "done", "cache_entries_at_end": len(cached),
          # programs compiled (>= 1 s) by this run: none on a second run
          "cache_entries_added": sorted(n.split("-")[0]
                                        for n in cached - cached_at_start),
          "wall_seconds_smoke": round(time.perf_counter() - t0, 1)})
    emit({"ok": True, "device": {"platform": device["platform"],
                                 "kind": device["kind"],
                                 "count": device["count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
