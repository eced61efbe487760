"""Benchmark: GPT-2 training throughput through the DeepSpeed-TPU engine.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": "tpu", "device_kind": ..., "device_count": N, ...}

Metric is tokens/sec/chip training GPT-2 (ZeRO-2, bf16) — the BASELINE.json
north-star axis. vs_baseline converts the achieved model FLOPS/chip
(6 * params * tokens/sec) against the reference's headline 64 TFLOPS/GPU
(BASELINE.md row 1, docs/_tutorials/bert-pretraining.md:387) — the only
published absolute compute-rate number in the reference docs.

Runs in the process that holds the chip, names the device it ran on,
and exits non-zero without a TPU or on any failure: there is no CPU
mode and no fallback.  (The shape-picking probe and the self-timed peak
are roadmap item S1's to replace.)
"""

from __future__ import annotations

import json
import os
import time

REFERENCE_TFLOPS = 64.0  # reference headline TFLOPS/GPU (BASELINE.md)

def _dense_peak_tflops(n=4096, iters=100) -> float:
    """Achievable bf16 MXU rate on this chip — the MFU denominator.

    Twin of tools/perf_sweep.py chip_matmul_tflops (bench.py must stay a
    standalone single file for the driver) — fix both together.

    The iteration chain lives INSIDE one jit (lax.fori_loop with a data
    dependency between matmuls), so the whole measurement is a single
    dispatch: a one-dispatch-per-matmul loop times the host, not the
    MXU."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(y, x):
        return jax.lax.fori_loop(
            0, iters, lambda i, y: jax.lax.dot(y, x), y)

    y = chain(x, x).block_until_ready()  # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        chain(y, x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return iters * 2 * n**3 / best / 1e12


def _device() -> dict:
    """The device this process holds, as JAX reports it; no TPU, no run."""
    import jax

    d = jax.devices()
    if d[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures on a TPU only; JAX found platform "
            f"{d[0].platform!r}")
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "device_count": len(d)}


def _time_config(size, seq, micro, remat, steps, warmup=2,
                 attn_impl="auto"):
    """Build an engine for one config and time `steps` steps. Returns the
    measurement dict, with every engine reference dropped afterwards so
    the next (possibly larger) config starts from a clean HBM."""
    import gc

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config

    n_dev = jax.device_count()
    cfg = gpt2_config(size, max_seq_len=seq,
                      shard_activations=n_dev > 1, remat=remat,
                      attn_impl=attn_impl)
    model = GPT(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config_params={
        "train_batch_size": micro * n_dev,
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": n_dev},
        "steps_per_print": 0,
    })
    n_params = model.num_params()
    global_batch = micro * n_dev
    tokens = jax.random.randint(jax.random.PRNGKey(0),
                                (global_batch, seq + 1), 0, cfg.vocab_size)
    batch = (tokens[:, :-1], tokens[:, 1:])

    def step():
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        return loss

    try:
        for _ in range(warmup):
            step().block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step()
        loss.block_until_ready()
        dt = time.perf_counter() - t0
    finally:
        # drop every engine/closure/array reference (same discipline as
        # run_headroom) before the caller builds the next engine
        try:
            del step, loss
        except UnboundLocalError:
            pass
        del engine, batch, tokens, model
        gc.collect()

    tok_s_chip = steps * global_batch * seq / dt / n_dev
    return {
        "size": size, "seq": seq, "micro": micro, "remat": remat,
        "attn_impl": attn_impl,
        "n_params": n_params, "n_dev": n_dev,
        "tok_s_chip": tok_s_chip,
        "tflops": 6.0 * n_params * tok_s_chip / 1e12,
    }


# headline candidates for the on-chip autotune probe: the fused
# single-chip step's MFU depends on model size x batch x remat in ways
# only hardware can rank (BERT-large at micro 64 measured 2x the MFU of
# GPT-2 small at micro 8 on an earlier chip). Probed cheaply (3 steps),
# winner gets the full measurement.
AUTOTUNE_CANDIDATES = (
    ("small", 8, False),   # the historical headline config
    ("small", 32, False),  # bigger batch, same model
    ("medium", 8, False),  # bigger matmuls, no recompute (if it fits)
    ("medium", 16, True),  # bigger matmuls + batch, remat for headroom
)


def run_bench() -> dict:
    import jax

    size, seq, micro, steps, remat = "small", 1024, 8, 20, False
    # sweep overrides (tools/perf_sweep.py drives these) pin the config
    # and disable the autotune probe
    pinned = any(k in os.environ for k in
                 ("DSTPU_BENCH_SIZE", "DSTPU_BENCH_MICRO",
                  "DSTPU_BENCH_SEQ"))
    size = os.environ.get("DSTPU_BENCH_SIZE", size)
    seq = int(os.environ.get("DSTPU_BENCH_SEQ", seq))
    micro = int(os.environ.get("DSTPU_BENCH_MICRO", micro))
    autotune = (not pinned
                and os.environ.get("DSTPU_BENCH_AUTOTUNE", "1") != "0")
    attn_impl = "auto"

    probes = []
    cached_hit = False
    cache_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "bench_artifacts", "autotune.json")
    # invalidation key: a cache probed under different candidates, seq,
    # or backend must not pin this run (e.g. new TPU generation)
    def _cache_fingerprint():
        import jax

        return {"candidates": [list(c) for c in AUTOTUNE_CANDIDATES],
                "seq": seq, "backend": jax.default_backend()}

    # the probe/cache state machine now lives in runtime/autotune
    # (SearchDriver: budgeted, failure-tolerant probe loop; WinnerCache
    # mode="single" keeps this exact autotune.json artifact format, so
    # committed bench artifacts stay comparable across rounds)
    from deepspeed_tpu.runtime.autotune import SearchDriver, WinnerCache

    if autotune:
        # a previous on-TPU session already probed: reuse its winner so
        # the driver's end-of-round run doesn't pay 3 extra compiles
        # against an unknown timeout budget
        cached = WinnerCache(cache_path,
                             mode="single").lookup(_cache_fingerprint())
        if cached is not None:
            try:
                # parse into temporaries FIRST: a truncated entry must
                # never half-clobber the default config before the
                # validation error fires
                c_size = cached["size"]
                c_micro = int(cached["micro"])
                c_remat = bool(cached["remat"])
                c_attn = cached.get("attn_impl", "auto")
            except (KeyError, TypeError, ValueError):
                pass  # foreign/truncated cache entry: re-probe below
            else:
                size, micro, remat, attn_impl = (c_size, c_micro, c_remat,
                                                 c_attn)
                autotune = False
                cached_hit = True
    if autotune:
        budget_s = float(os.environ.get("DSTPU_AUTOTUNE_BUDGET_S", "420"))

        def _probe(cand):
            return _time_config(cand["size"], seq, cand["micro"],
                                cand["remat"], steps=3, warmup=1,
                                attn_impl=cand.get("attn_impl", "auto"))

        def _fmt(res):
            """Format-stable probes-list entry (the committed artifact
            shape): success = the rounded metrics, failure/skip = the
            candidate + why (A/B entries carry attn_impl only)."""
            cand = dict(res.candidate)
            ab = "attn_impl" in cand
            if res.skipped is not None:
                return {**cand, "skipped": res.skipped}
            if res.error is not None:
                if ab:
                    return {"attn_impl": cand["attn_impl"],
                            "failed": res.error}
                return {**cand, "failed": res.error, "oom": res.oom}
            return {k: (round(v, 2) if isinstance(v, float) else v)
                    for k, v in res.metrics.items()
                    if k not in ("n_params", "n_dev")}

        driver = SearchDriver(_probe, score_fn=lambda m: m["tflops"],
                              budget_s=budget_s)
        best = driver.search([{"size": c_size, "micro": c_micro,
                               "remat": c_remat}
                              for c_size, c_micro, c_remat in
                              AUTOTUNE_CANDIDATES])
        if best is not None:
            size, micro, remat = (best.metrics["size"],
                                  best.metrics["micro"],
                                  best.metrics["remat"])
            # kernel-choice A/B at the winning shape: the flash-vs-XLA
            # attention question has no hardware datum yet — one extra
            # probe settles it for the final measurement
            if not driver.budget_exhausted():
                r_ab = driver.probe({"size": size, "micro": micro,
                                     "remat": remat, "attn_impl": "xla"})
                if r_ab.ok and r_ab.metrics["tflops"] > \
                        best.metrics["tflops"]:
                    attn_impl = "xla"
        probes = [_fmt(r) for r in driver.results]
        if best is not None and driver.complete:
            # never pin future rounds to a degraded probe set
            WinnerCache(cache_path, mode="single").store(
                _cache_fingerprint(),
                {"size": size, "micro": micro, "remat": remat,
                 "attn_impl": attn_impl}, probes)

    try:
        r = _time_config(size, seq, micro, remat, steps=steps,
                         attn_impl=attn_impl)
    except Exception:
        # a cached/probed winner that no longer runs (chip change, OOM)
        # must not kill the headline: fall back to the known-good default
        if (size, micro, remat) == ("small", 8, False):
            raise
        size, micro, remat = "small", 8, False
        cached_hit = False
        attn_impl = "auto"
        r = _time_config(size, seq, micro, remat, steps=steps)
    tokens_per_sec_chip = r["tok_s_chip"]
    achieved_tflops = r["tflops"]
    peak = _dense_peak_tflops()

    out = {
        "metric": f"gpt2_{size}_zero2_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(achieved_tflops / REFERENCE_TFLOPS, 4),
        **_device(),
        "tflops_per_chip": round(achieved_tflops, 2),
        "world_size": r["n_dev"],
        "micro_batch": micro,
        "seq_len": seq,
    }
    if r["remat"]:
        out["remat"] = True
    if r["attn_impl"] != "auto":
        out["attn_impl"] = r["attn_impl"]
    if probes:
        out["autotune_probes"] = probes
    if cached_hit:
        out["autotune_cached"] = True  # config provenance: prior session
    if peak:
        # MFU against this chip's MEASURED dense bf16 matmul rate (the
        # vs_baseline denominator stays the reference's published 64
        # TFLOPS/GPU so the driver metric is comparable across rounds)
        out["chip_dense_tflops"] = round(peak, 1)
        out["mfu_pct"] = round(100 * achieved_tflops / peak, 1)
    if r["n_dev"] == 1:
        out["note"] = ("world_size=1: ZeRO dp-sharding inactive; measures "
                       "the fused single-chip step only")
    return out


def run_headroom() -> dict:
    """Memory-headroom mode (DSTPU_BENCH_MODE=headroom): largest micro
    batch that fits on ONE chip for a mid-size GPT with remat + streaming
    CE, and the MFU at that batch — on-hardware evidence for the
    memory-first kernels that ZeRO can't show at world_size=1."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config

    size, seq, tries = "medium", 1024, (1, 2, 4, 8, 16, 32, 64)
    size = os.environ.get("DSTPU_BENCH_SIZE", size)
    seq = int(os.environ.get("DSTPU_BENCH_SEQ", seq))

    cfg = gpt2_config(size, max_seq_len=seq, remat=True,
                      shard_activations=False)
    n_params = GPT(cfg).num_params()
    best = None  # (micro, tokens_per_sec)
    for micro in tries:
        try:
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=GPT(cfg), config_params={
                    "train_batch_size": micro,
                    "bf16": {"enabled": True},
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                    "zero_optimization": {"stage": 0},
                    "mesh": {"data": 1},
                    "steps_per_print": 0,
                })
            tokens = jax.random.randint(jax.random.PRNGKey(0),
                                        (micro, seq + 1), 0, cfg.vocab_size)
            batch = (tokens[:, :-1], tokens[:, 1:])

            def step():
                loss = engine.forward(batch)
                engine.backward()
                engine.step()
                return loss

            step().block_until_ready()  # compile + first step (peak alloc)
            n_steps = 8
            t0 = time.perf_counter()
            for _ in range(n_steps):
                loss = step()
            loss.block_until_ready()
            dt = time.perf_counter() - t0
            best = (micro, n_steps * micro * seq / dt)
            # drop EVERY reference to this engine's device memory before
            # the next (larger) engine allocates: the step closure and
            # loss array both capture it, so `del engine` alone would
            # leave both models resident and OOM the search early
            del step, loss, engine
            import gc

            gc.collect()
        except Exception as exc:
            if "RESOURCE_EXHAUSTED" in str(exc) or "Out of memory" in str(exc):
                break  # found the ceiling
            raise
    if best is None:
        raise RuntimeError("no micro batch fit")
    micro, tps = best
    search_capped = micro == tries[-1]  # never hit OOM: not a true ceiling
    achieved = 6.0 * n_params * tps / 1e12
    peak = _dense_peak_tflops()
    out = {
        "metric": f"gpt2_{size}_headroom_max_micro_batch",
        "value": micro,
        "unit": "micro_batch (remat + streaming CE, 1 chip)",
        "vs_baseline": round(achieved / REFERENCE_TFLOPS, 4),
        **_device(),
        "tokens_per_sec_chip": round(tps, 1),
        "tflops_per_chip": round(achieved, 2),
        "seq_len": seq,
    }
    if peak:
        out["chip_dense_tflops"] = round(peak, 1)
        out["mfu_pct"] = round(100 * achieved / peak, 1)
    if search_capped:
        out["search_capped"] = True  # largest TRIED batch fit; not an OOM ceiling
    return out


def _record_artifact(result: dict) -> dict:
    """Land the result in the committed, manifest-indexed artifact dir
    (deepspeed_tpu/monitor/artifacts.py) so a hardware measurement
    survives the session that produced it — the round-5 failure mode
    (on-TPU artifacts later deleted from the tree, docs pointing at
    nothing) cannot recur when every run writes through the manifest.
    Telemetry must never kill the headline: best-effort only."""
    try:
        from deepspeed_tpu.monitor.artifacts import record_bench_result

        result["artifact"] = record_bench_result(result)
    except Exception:
        pass
    return result


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    _device()  # no TPU: raise before anything compiles
    mode = os.environ.get("DSTPU_BENCH_MODE", "throughput")
    result = (run_headroom if mode == "headroom" else run_bench)()
    print(json.dumps(_record_artifact(result)))


if __name__ == "__main__":
    main()
