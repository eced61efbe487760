#!/usr/bin/env python
"""Serve bench: continuous batching vs static batching under Poisson
arrivals.

The headline serving claim: a continuous-batching engine (in-flight
admission over the paged KV cache, deepspeed_tpu/serving/) sustains
more tokens/s at equal-or-better tail latency than classic static
batching, because slots and KV blocks freed by a finished request are
refilled the SAME step instead of draining the batch to its longest
member.  This tool runs that claim as a bench:

* one request timeline (seeded Poisson inter-arrivals, varied prompt
  lengths and token budgets) replayed against TWO engines that differ
  only in the admission policy (`continuous` vs `static`);
* arrivals land from a submitter thread while a `ServeWorker` drives
  the engine — real wall-clock, real overlap of admission and decode;
* per-lane metrics: decoded tokens/s over the makespan, p50/p99
  time-to-first-token, p50/p99 inter-token latency, mean/peak KV block
  occupancy, plus the serve.*/kv.* counter deltas.

Artifacts (the PR-2 rule): a flat result JSON via
monitor/artifacts.record_bench_result PLUS a run directory
`bench_artifacts/runs/<stamp>_serve_bench/serving.json` that
`tools/run_report.py <dir>` renders as the "Serving bench" table.

Campaigns:

* default — the full two-lane Poisson comparison.
* `--spec` — the speculative-decoding campaign: one repetitive-suffix
  greedy Poisson timeline against every (kv_dtype x draft_len) lane
  (accepted tokens/step, tok/s, TTFT/ITL tails per lane; outputs
  asserted token-identical across draft_len at matched kv_dtype), plus
  the equal-pool-bytes resident-session pair (bf16 vs int8 KV at the
  same byte budget, peak concurrently resident sessions compared).
* `--fleet` — the prefix-cache + fleet-router campaign: ONE
  shared-prefix greedy Poisson timeline through a prefix-cache-OFF
  single engine (the cold baseline AND the bitwise oracle) and a
  FleetRouter lane per replica count (tok/s, p50/p99 TTFT, cache-hit
  rate vs replica count), plus warm-pinned-session vs cold-turn
  multi-turn lanes (turn>=2 TTFT, prefill tokens actually computed).
  Every cache-on lane asserts bitwise identity to the cache-off
  oracle; the recorded campaign additionally asserts hit rate > 50%
  and warm < cold TTFT p50 so a sub-claim artifact cannot commit.
* `--dry-run` — a seconds-scale miniature of the same two lanes, wired
  into tier-1 via tests/test_serving.py so the bench cannot rot.
* `--dry-run --fleet` / `run_dry_fleet()` (tests/test_serving.py) —
  the tier-1 fleet miniature: cache-off oracle + 1/2-replica fleets +
  session lanes, pinning the deterministic claims (bitwise identity,
  nonzero hit rate, session pins engaging, warm lane computing fewer
  prefill tokens).
* `--dry-run --spec` / `run_dry_spec()` (tests/test_spec_decode.py) —
  the tier-1 spec miniature: the (kv_dtype x draft_len) sweep with
  BITWISE oracles — dense/bf16 lanes pinned token-identical to
  `generate()` / `generate(cache_dtype=bf16)`.
* `run_dry_chaos()` (tests/test_serving.py) — the chaos lane: a
  FaultPlan hangs a decode step, the StepWatchdog trips and sheds the
  wedged batch, the remaining requests complete with oracle-identical
  outputs.

Usage: python tools/serve_bench.py [--dry-run] [--spec] [--trace]
           [--requests 48] [--rate 24.0] [--seed 0] [--no-record]

`--trace` (docs/tutorials/tracing.md) attaches a
monitor.tracing.TraceRecorder + ServingSLO to the continuous lane: the
per-request timeline (queue_wait -> prefill chunks -> first_token ->
decode steps -> finish) lands as trace.rank00000.jsonl beside
serving.json, SLO windows as events.rank00000.jsonl, and the run dir
becomes input for both tools/run_report.py (the "Serving SLO" section)
and tools/trace_report.py (the merged Perfetto timeline).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

SERVING_SCHEMA_VERSION = 1


def _percentile(xs, q):
    """Nearest-rank percentile: the smallest sample with at least q%
    of the distribution at or below it — `ceil(q/100 * n) - 1` into
    the sorted list, no interpolation.  Deterministic and always an
    observed latency (an interpolated p99 can name a latency no
    request ever saw).  Pinned by tests/test_spec_decode.py: p50 of
    [1..4] is 2, p100 is the max, p99 of 100 samples is the 99th
    sorted value."""
    if not xs:
        return None
    xs = sorted(xs)
    idx = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return xs[min(idx, len(xs) - 1)]


def build_timeline(n_requests: int, rate_hz: float, seed: int,
                   vocab: int, prompt_range=(4, 24), new_range=(4, 32)):
    """Seeded Poisson arrival timeline: [(t_arrival_s, prompt, max_new,
    temperature, top_k, seed)] — identical for every lane."""
    import numpy as np

    rng = np.random.RandomState(seed)
    t = 0.0
    timeline = []
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate_hz))
        p_len = int(rng.randint(*prompt_range))
        prompt = rng.randint(0, vocab, (p_len,)).tolist()
        max_new = int(rng.randint(*new_range))
        temp = float(rng.choice([0.0, 0.7, 1.0]))
        timeline.append((t, prompt, max_new, temp, 8, 1000 + i))
    return timeline


def build_spec_timeline(n_requests: int, rate_hz: float, seed: int,
                        vocab: int, pattern_range=(3, 6), repeats=4,
                        new_range=(24, 48), burst=False):
    """Seeded Poisson timeline of REPETITIVE-SUFFIX greedy prompts:
    each prompt is a short random pattern tiled `repeats` times — the
    workload self-speculative decoding exists for (greedy decode over
    a repeating context keeps extending the cycle, so the n-gram
    drafter's suffix match predicts it and most drafts verify).
    `burst=True` lands every arrival at ~t=0 (the resident-session
    lanes measure concurrency under a thundering herd, not a rate)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    t = 0.0
    timeline = []
    for i in range(n_requests):
        t += 0.0 if burst else float(rng.exponential(1.0 / rate_hz))
        m = int(rng.randint(*pattern_range))
        pat = rng.randint(0, vocab, (m,)).tolist()
        prompt = pat * repeats
        max_new = int(rng.randint(*new_range))
        timeline.append((t, prompt, max_new, 0.0, 0, 1000 + i))
    return timeline


def _nano_model(vocab=128, max_seq=128, layers=2, d_model=64, heads=4):
    import jax

    from deepspeed_tpu.models import GPT, gpt2_config

    model = GPT(gpt2_config("nano", num_layers=layers, num_heads=heads,
                            d_model=d_model, vocab_size=vocab,
                            max_seq_len=max_seq))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def run_lane(model, params, serve_cfg, timeline, programs=None,
             watchdog=None, tracing=None):
    """Replay `timeline` against one engine; returns (metrics, engine).
    `tracing` is an optional (TraceRecorder, ServingSLO) pair attached
    via engine.attach_tracing — the --trace lane."""
    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.serving import ServeEngine, ServeWorker

    eng = ServeEngine(model, params, serve_cfg, programs=programs)
    if watchdog is not None:
        eng.attach_watchdog(watchdog)
    if tracing is not None:
        eng.attach_tracing(tracer=tracing[0], slo=tracing[1])
    worker = ServeWorker(eng)
    snap = COUNTERS.snapshot()
    worker.start()
    t0 = time.monotonic()
    reqs = []
    try:
        for t_arr, prompt, max_new, temp, top_k, seed in timeline:
            delay = t0 + t_arr - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            reqs.append(eng.submit(prompt, max_new, temperature=temp,
                                   top_k=top_k, seed=seed))
        while eng.has_work() and worker.is_alive():
            time.sleep(0.005)
    finally:
        worker.stop()
        eng.close()
    delta = COUNTERS.delta_since(snap)

    done = [r for r in reqs if r.state == "finished"]
    errored = [r for r in reqs if r.state == "error"]
    ttfts = [r.ttft_s * 1000.0 for r in done if r.ttft_s is not None]
    itls = []
    for r in done:
        itls.extend((b - a) * 1000.0
                    for a, b in zip(r.token_times, r.token_times[1:]))
    n_tokens = sum(len(r.out) for r in done)
    makespan = max((r.t_finish for r in done if r.t_finish is not None),
                   default=t0) - t0
    kv_samples = delta.get("kv.blocks_in_use", {})
    mean_blocks = (kv_samples.get("bytes", 0) / kv_samples["calls"]
                   if kv_samples.get("calls") else 0.0)
    metrics = {
        "requests": len(reqs),
        "completed": len(done),
        "errored": len(errored),
        "tokens": n_tokens,
        "makespan_s": round(makespan, 3),
        "tokens_per_sec": round(n_tokens / makespan, 2) if makespan else None,
        "ttft_ms": {"p50": round(_percentile(ttfts, 50), 2) if ttfts else None,
                    "p99": round(_percentile(ttfts, 99), 2) if ttfts else None,
                    "mean": round(sum(ttfts) / len(ttfts), 2) if ttfts
                    else None},
        "itl_ms": {"p50": round(_percentile(itls, 50), 2) if itls else None,
                   "p99": round(_percentile(itls, 99), 2) if itls else None},
        "kv_blocks": {"mean": round(mean_blocks, 2),
                      "peak": eng.peak_blocks_in_use,
                      "capacity": eng.kv.capacity_blocks},
        "decode_steps": delta.get("serve.decode_steps", {}).get("calls", 0),
        "shed": delta.get("serve.shed", {}).get("calls", 0),
        "prefix_hit_rate": _hit_rate(delta),
        "prefix_hit_tokens": delta.get("kv.prefix_hit_tokens",
                                       {}).get("bytes", 0),
        "kv_dtype": eng.kv.quant_wire or
        (str(serve_cfg.kv_dtype) if serve_cfg.kv_dtype is not None
         else "dense"),
        "draft_len": int(serve_cfg.draft_len),
        "counters": delta,
        # per-request outputs in submit order — the dry lanes' bitwise
        # oracle material; stripped from artifacts by record_serving
        "outputs": [list(r.out) for r in reqs],
    }
    if int(serve_cfg.draft_len) > 0:
        steps = metrics["decode_steps"]
        acc = delta.get("serve.accepted_tokens", {}).get("calls", 0)
        metrics["draft_tokens"] = \
            delta.get("serve.draft_tokens", {}).get("calls", 0)
        metrics["accepted_tokens"] = acc
        # extra tokens each verify step bought on top of the 1 a plain
        # decode step always yields — the speculative headline number
        metrics["accepted_per_step"] = \
            round(acc / steps, 3) if steps else 0.0
    if eng.kv.quant_wire:
        dq = delta.get("kv.dequant_ms", {})
        metrics["dequant_ms"] = round(dq.get("bytes", 0) / 1000.0, 2)
    return metrics, eng


def run_campaign(n_requests=48, rate_hz=24.0, seed=0, record=True,
                 dry=False, trace=False):
    """The two-lane comparison; returns the result dict.

    `trace=True` runs the CONTINUOUS lane with a TraceRecorder +
    ServingSLO attached (monitor/tracing.py): the per-request timeline
    lands in trace.rank00000.jsonl and the SLO windows in
    events.rank00000.jsonl beside serving.json, so
    `tools/run_report.py <run_dir>` renders a "Serving SLO" section
    whose window-covering-the-lane p50/p99 TTFT reproduces this
    bench's own nearest-rank numbers, and
    `tools/trace_report.py <run_dir>` merges the request timeline into
    Chrome/Perfetto JSON."""
    import jax

    from deepspeed_tpu.serving import ServeConfig

    if dry:
        n_requests, rate_hz = min(n_requests, 6), max(rate_hz, 8.0)
        model, params = _nano_model(vocab=64, max_seq=64, d_model=32)
        mk_cfg = lambda adm: ServeConfig(
            block_size=4, num_blocks=48, max_batch=3, prefill_chunk=8,
            max_seq_len=64, admission=adm)
        timeline = build_timeline(n_requests, rate_hz, seed, 64,
                                  prompt_range=(3, 10), new_range=(3, 10))
    else:
        # sized so arrivals SATURATE the engine on the CPU lane (~3.6
        # ms/decode-step at full batch): the admission policies only
        # differentiate under queueing pressure
        model, params = _nano_model(vocab=512, max_seq=256, layers=4,
                                    d_model=128, heads=8)
        mk_cfg = lambda adm: ServeConfig(
            block_size=8, num_blocks=128, max_batch=4, prefill_chunk=16,
            max_seq_len=256, admission=adm)
        timeline = build_timeline(n_requests, rate_hz, seed, 512,
                                  prompt_range=(4, 32),
                                  new_range=(16, 96))

    # warm the compile cache OUTSIDE the timed lanes: both lanes share
    # one (prefill, decode) program pair, so neither pays XLA
    # compilation against its latency numbers
    from deepspeed_tpu.serving import ServeEngine

    warm = ServeEngine(model, params, mk_cfg("continuous"))
    warm.generate([timeline[0][1]], 2)
    programs = warm.programs
    del warm

    trace_tmp, slo_events, slo_final = None, [], None
    lanes = {}
    for adm in ("continuous", "static"):
        tracing = None
        if trace and adm == "continuous":
            import tempfile

            from deepspeed_tpu.monitor.tracing import (ServingSLO,
                                                       TraceRecorder)

            trace_tmp = tempfile.mkdtemp(prefix="serve_trace_")
            rec = TraceRecorder(trace_tmp, flush_interval_s=0.2)
            # window wide enough to cover the whole lane: the final
            # forced snapshot then aggregates EVERY request, so its
            # nearest-rank p50/p99 must equal the bench's own
            slo = ServingSLO(
                emit=lambda snap: slo_events.append(
                    {"v": 1, "type": "slo", "rank": 0,
                     "t": time.time(), "slo": snap}),
                window_s=1e6, emit_interval_s=0.25, tracer=rec)
            tracing = (rec, slo)
        print(f"--- lane: {adm} batching ({n_requests} requests, "
              f"Poisson {rate_hz:.1f}/s) ---")
        metrics, _eng = run_lane(model, params, mk_cfg(adm), timeline,
                                 programs=programs, tracing=tracing)
        if tracing is not None:
            slo_final = tracing[1].force()
            slo_events.append({"v": 1, "type": "slo", "rank": 0,
                               "t": time.time(), "slo": slo_final})
            tracing[0].close()
            metrics["slo"] = slo_final
            # the SLO window covered the lane, so its nearest-rank
            # percentiles must reproduce the bench's — pinned here so
            # the traced artifact can never disagree with its own table
            for q in ("p50", "p99"):
                bench_q, slo_q = metrics["ttft_ms"][q], \
                    slo_final["ttft_ms"][q]
                assert bench_q is None or \
                    abs(slo_q - bench_q) < 0.005 + 1e-9, \
                    (q, bench_q, slo_q)
        lanes[adm] = metrics
        print(f"    {metrics['completed']}/{metrics['requests']} done, "
              f"{metrics['tokens']} tok in {metrics['makespan_s']}s = "
              f"{metrics['tokens_per_sec']} tok/s; TTFT p50/p99 "
              f"{metrics['ttft_ms']['p50']}/{metrics['ttft_ms']['p99']} ms; "
              f"ITL p50/p99 {metrics['itl_ms']['p50']}/"
              f"{metrics['itl_ms']['p99']} ms; KV mean/peak "
              f"{metrics['kv_blocks']['mean']}/"
              f"{metrics['kv_blocks']['peak']}")

    outputs = {name: m.pop("outputs") for name, m in lanes.items()}
    cont, stat = lanes["continuous"], lanes["static"]
    result = {
        "metric": "serve_bench",
        "platform": jax.default_backend(),
        "dry_run": dry,
        "n_requests": n_requests,
        "rate_hz": rate_hz,
        "seed": seed,
        "model": {"layers": model.config.num_layers,
                  "d_model": model.config.d_model,
                  "heads": model.config.num_heads,
                  "vocab": model.config.vocab_size},
        "lanes": lanes,
        "value": cont["tokens_per_sec"],
        "unit": "tokens/s (continuous)",
        "speedup_tokens_per_sec": (
            round(cont["tokens_per_sec"] / stat["tokens_per_sec"], 3)
            if stat["tokens_per_sec"] else None),
    }
    if record:
        result["artifact"], result["run_dir"] = record_serving(result)
        print(f"artifact: {result['artifact']}")
        print(f"report:   python tools/run_report.py {result['run_dir']}")
        if trace_tmp is not None:
            run_dir = os.path.join(os.path.dirname(HERE),
                                   result["run_dir"])
            _install_trace(trace_tmp, slo_events, run_dir)
            trace_tmp = run_dir
            print(f"trace:    python tools/trace_report.py "
                  f"{result['run_dir']}")
    if trace_tmp is not None:
        # recorded: the run dir now holds the trace; unrecorded (the
        # tier-1 dry lane): the raw temp dir — run_dry asserts on it
        # and cleans up
        result["trace"] = {"dir": trace_tmp, "slo_events": slo_events,
                           "slo": slo_final}
    result["outputs"] = outputs  # post-record: oracle material only
    return result


def _install_trace(trace_tmp, slo_events, run_dir):
    """Move the traced lane's files into the recorded run dir:
    trace.rank*.jsonl (for tools/trace_report.py) + an
    events.rank00000.jsonl of slo events (for run_report's "Serving
    SLO" section)."""
    import glob
    import shutil

    os.makedirs(run_dir, exist_ok=True)
    for path in glob.glob(os.path.join(trace_tmp, "trace.rank*.jsonl")):
        shutil.move(path, os.path.join(run_dir, os.path.basename(path)))
    with open(os.path.join(run_dir, "events.rank00000.jsonl"), "w") as f:
        for ev in slo_events:
            f.write(json.dumps(ev) + "\n")
    shutil.rmtree(trace_tmp, ignore_errors=True)


def _print_lane(name, m):
    spec = (f"; +{m['accepted_per_step']:.2f} accepted tok/step"
            if "accepted_per_step" in m else "")
    print(f"    {name}: {m['completed']}/{m['requests']} done, "
          f"{m['tokens']} tok in {m['makespan_s']}s = "
          f"{m['tokens_per_sec']} tok/s; TTFT p50/p99 "
          f"{m['ttft_ms']['p50']}/{m['ttft_ms']['p99']} ms; ITL p50/p99 "
          f"{m['itl_ms']['p50']}/{m['itl_ms']['p99']} ms{spec}")


def run_spec_campaign(n_requests=32, rate_hz=64.0, seed=0, record=True,
                      dry=False, kv_dtypes=(None, "bf16", "int8", "int4"),
                      draft_lens=(0, 4)):
    """The speculative-decoding campaign: ONE repetitive-suffix greedy
    Poisson timeline replayed against every (kv_dtype x draft_len)
    lane, plus the equal-pool-bytes resident-session pair.  Headline
    claims: draft=4 buys >= 1.3x tokens/s over draft=0 at
    matched kv_dtype with > 1.5 accepted tokens/step on this workload,
    and int8 KV keeps >= 1.5x more sessions concurrently resident than
    bf16 at the SAME pool byte budget.  Output is token-identical
    across draft_len at matched kv_dtype by construction — the bench
    asserts it on every lane pair, so the speed claim can never drift
    from the correctness claim."""
    import jax

    from deepspeed_tpu.serving import ServeConfig, ServeEngine

    if dry:
        n_requests = min(n_requests, 5)
        model, params = _nano_model(vocab=64, max_seq=64, d_model=32)
        vocab = 64
        mk = lambda kvd, draft: ServeConfig(
            block_size=4, num_blocks=48, max_batch=3, prefill_chunk=8,
            max_seq_len=64, kv_dtype=kvd, draft_len=draft)
        # fixed pattern/budget sizes -> every request shares one shape,
        # so the run_dry_spec generate() oracle compiles ONCE per dtype
        timeline = build_spec_timeline(n_requests, max(rate_hz, 8.0),
                                       seed, vocab,
                                       pattern_range=(4, 5), repeats=3,
                                       new_range=(10, 11))
    else:
        # ONE decode slot: speculative decoding is a latency-bound-lane
        # optimisation — it spends one dispatch's fixed overhead on
        # draft_len+1 positions of the SAME stream, exactly what a full
        # decode batch already amortises across slots (at max_batch 4
        # on this fabric the two cancel out and spec is a wash; the
        # single-stream lane is where the win honestly lives)
        model, params = _nano_model(vocab=256, max_seq=256, layers=2,
                                    d_model=64, heads=4)
        vocab = 256
        mk = lambda kvd, draft: ServeConfig(
            block_size=8, num_blocks=128, max_batch=1, prefill_chunk=16,
            max_seq_len=256, kv_dtype=kvd, draft_len=draft)
        timeline = build_spec_timeline(n_requests, rate_hz, seed, vocab,
                                       pattern_range=(3, 6), repeats=5,
                                       new_range=(48, 96))

    lanes = {}
    for kvd in kv_dtypes:
        for draft in draft_lens:
            name = f"{kvd or 'dense'}_d{draft}"
            cfg = mk(kvd, draft)
            # warm the (prefill, decode, verify) compile cache outside
            # the timed lane, like run_campaign does
            warm = ServeEngine(model, params, cfg)
            warm.generate([timeline[0][1]], 2)
            programs = warm.programs
            del warm
            print(f"--- spec lane: kv={kvd or 'dense'} draft={draft} "
                  f"({len(timeline)} requests) ---")
            metrics, _eng = run_lane(model, params, cfg, timeline,
                                     programs=programs)
            lanes[name] = metrics
            _print_lane(name, metrics)

    # token-identity across draft_len at matched kv_dtype — the spec
    # invariant, asserted on the bench's own numbers
    for kvd in kv_dtypes:
        base = f"{kvd or 'dense'}_d{draft_lens[0]}"
        for draft in draft_lens[1:]:
            other = f"{kvd or 'dense'}_d{draft}"
            assert lanes[base]["outputs"] == lanes[other]["outputs"], \
                f"speculation changed tokens: {base} vs {other}"

    spec_speedup = {}
    for kvd in kv_dtypes:
        key = kvd or "dense"
        base = lanes[f"{key}_d{draft_lens[0]}"]
        top = lanes[f"{key}_d{max(draft_lens)}"]
        if base["tokens_per_sec"] and top["tokens_per_sec"]:
            spec_speedup[key] = round(
                top["tokens_per_sec"] / base["tokens_per_sec"], 3)

    res_lanes, resident = run_resident_lanes(model, params, seed=seed,
                                             dry=dry)
    lanes.update(res_lanes)

    outputs = {name: m.pop("outputs") for name, m in lanes.items()}
    result = {
        "metric": "serve_spec_bench",
        "platform": jax.default_backend(),
        "dry_run": dry,
        "n_requests": len(timeline),
        "rate_hz": rate_hz,
        "seed": seed,
        "model": {"layers": model.config.num_layers,
                  "d_model": model.config.d_model,
                  "heads": model.config.num_heads,
                  "vocab": model.config.vocab_size},
        "lanes": lanes,
        "spec_speedup_tokens_per_sec": spec_speedup,
        "resident_sessions": resident,
        "value": max(spec_speedup.values()) if spec_speedup else None,
        "unit": "x tokens/s (spec vs draft=0, best kv lane)",
    }
    if record:
        result["artifact"], result["run_dir"] = record_serving(result)
        print(f"artifact: {result['artifact']}")
        print(f"report:   python tools/run_report.py {result['run_dir']}")
    result["outputs"] = outputs  # post-record: oracle material only
    return result


def run_resident_lanes(model, params, seed=0, dry=False):
    """Equal-pool-bytes sizing lanes: bf16 vs int8 KV given the SAME
    byte budget.  int8's smaller blocks (head_dim + 2 scale bytes vs
    2*head_dim) buy ~2*Dh/(Dh+2) x more blocks, so under a burst of
    long decodes the int8 engine keeps proportionally more sessions
    concurrently resident (engine.peak_resident) — the second half of
    the quantized-KV claim, the first being token fidelity."""
    from deepspeed_tpu.serving import (ServeConfig, kv_block_bytes)

    cfg = model.config
    bs, bf_cap = (4, 10) if dry else (8, 48)
    n, max_new = (12, 12) if dry else (24, 56)
    prompt_pat = (2, 3) if dry else (4, 5)
    bf_bb = kv_block_bytes(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                           bs, "bf16")
    i8_bb = kv_block_bytes(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                           bs, "int8")
    pool_bytes = bf_cap * bf_bb
    i8_cap = pool_bytes // i8_bb
    timeline = build_spec_timeline(n, 1.0, seed + 1,
                                   model.config.vocab_size,
                                   pattern_range=prompt_pat, repeats=2,
                                   new_range=(max_new, max_new + 1),
                                   burst=True)
    lanes = {}
    for name, kvd, cap, bb in (("resident_bf16", "bf16", bf_cap, bf_bb),
                               ("resident_int8", "int8", i8_cap, i8_bb)):
        scfg = ServeConfig(block_size=bs, num_blocks=int(cap) + 1,
                           max_batch=n, prefill_chunk=bs * 2,
                           max_seq_len=model.config.max_seq_len,
                           kv_dtype=kvd)
        print(f"--- resident lane: kv={kvd}, {cap} blocks x {bb} B "
              f"(pool {cap * bb:,} B), {n}-request burst ---")
        metrics, eng = run_lane(model, params, scfg, timeline)
        metrics["peak_resident"] = eng.peak_resident
        metrics["pool_bytes"] = int(cap * bb)
        lanes[name] = metrics
        print(f"    peak resident sessions: {eng.peak_resident}")
    peak_bf = lanes["resident_bf16"]["peak_resident"]
    peak_i8 = lanes["resident_int8"]["peak_resident"]
    resident = {
        "pool_bytes_budget": int(pool_bytes),
        "bf16": {"blocks": int(bf_cap), "block_bytes": int(bf_bb),
                 "peak_resident": peak_bf},
        "int8": {"blocks": int(i8_cap), "block_bytes": int(i8_bb),
                 "peak_resident": peak_i8},
        "resident_ratio": round(peak_i8 / peak_bf, 3) if peak_bf else None,
    }
    return lanes, resident


def build_prefix_timeline(n_requests: int, rate_hz: float, seed: int,
                          vocab: int, n_prefixes=4, prefix_len=16,
                          tail_range=(2, 6), new_range=(8, 16)):
    """Seeded Poisson timeline of SHARED-PREFIX greedy prompts: each
    request draws one of `n_prefixes` fixed prefixes and appends a
    short random tail — the workload block-level prefix caching exists
    for (a few system prompts fanned out across user turns).  Greedy
    (temperature 0) so the cache-on lanes have a bitwise cache-off
    oracle."""
    import numpy as np

    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(0, vocab, (prefix_len,)).tolist()
                for _ in range(n_prefixes)]
    t = 0.0
    timeline = []
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate_hz))
        pre = prefixes[int(rng.randint(0, n_prefixes))]
        tail = rng.randint(
            0, vocab, (int(rng.randint(*tail_range)),)).tolist()
        max_new = int(rng.randint(*new_range))
        timeline.append((t, pre + tail, max_new, 0.0, 0, 1000 + i))
    return timeline


def _hit_rate(delta):
    """Fraction of prefill tokens served from the prefix cache:
    skipped / (skipped + computed), from the counter delta
    (kv.prefix_hit_tokens bytes vs serve.prefill_chunks bytes)."""
    hit = delta.get("kv.prefix_hit_tokens", {}).get("bytes", 0)
    computed = delta.get("serve.prefill_chunks", {}).get("bytes", 0)
    total = hit + computed
    return round(hit / total, 4) if total else 0.0


def run_fleet_lane(model, params, serve_cfg, timeline, replicas,
                   programs=None, queue_limit=64):
    """Replay `timeline` through a FleetRouter over `replicas`
    in-process engines (one ServeWorker each); returns the lane
    metrics dict, engines closed."""
    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.serving import FleetRouter, build_fleet

    engines = build_fleet(model, params, serve_cfg, replicas=replicas,
                          programs=programs)
    router = FleetRouter(engines, queue_limit=queue_limit)
    snap = COUNTERS.snapshot()
    router.start()
    t0 = time.monotonic()
    reqs = []
    try:
        for t_arr, prompt, max_new, temp, top_k, seed in timeline:
            delay = t0 + t_arr - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            reqs.append(router.submit(prompt, max_new, temperature=temp,
                                      top_k=top_k, seed=seed))
        while router.has_work():
            time.sleep(0.005)
    finally:
        router.close()
    delta = COUNTERS.delta_since(snap)

    done = [r for r in reqs if r.state == "finished"]
    errored = [r for r in reqs if r.state == "error"]
    ttfts = [r.ttft_s * 1000.0 for r in done if r.ttft_s is not None]
    itls = []
    for r in done:
        itls.extend((b - a) * 1000.0
                    for a, b in zip(r.token_times, r.token_times[1:]))
    n_tokens = sum(len(r.out) for r in done)
    makespan = max((r.t_finish for r in done if r.t_finish is not None),
                   default=t0) - t0
    per_replica = [0] * replicas
    for r in reqs:
        i = getattr(r, "replica", None)
        if i is not None:
            per_replica[i] += 1
    return {
        "replicas": replicas,
        "requests": len(reqs),
        "completed": len(done),
        "errored": len(errored),
        "tokens": n_tokens,
        "makespan_s": round(makespan, 3),
        "tokens_per_sec": round(n_tokens / makespan, 2) if makespan
        else None,
        "ttft_ms": {
            "p50": round(_percentile(ttfts, 50), 2) if ttfts else None,
            "p99": round(_percentile(ttfts, 99), 2) if ttfts else None,
            "mean": round(sum(ttfts) / len(ttfts), 2) if ttfts
            else None},
        "itl_ms": {
            "p50": round(_percentile(itls, 50), 2) if itls else None,
            "p99": round(_percentile(itls, 99), 2) if itls else None},
        "prefix_hit_rate": _hit_rate(delta),
        "prefix_hits": delta.get("kv.prefix_hits", {}).get("calls", 0),
        "prefix_hit_tokens": delta.get("kv.prefix_hit_tokens",
                                       {}).get("bytes", 0),
        "cow_copies": delta.get("kv.cow_copies", {}).get("calls", 0),
        "dispatch_per_replica": per_replica,
        "spills": router.spilled,
        "shed_router": router.shed,
        "counters": delta,
        "outputs": [list(r.out) for r in reqs],
    }


def run_session_lanes(model, params, seed=0, dry=False, programs=None):
    """Warm pinned-session turns vs cold re-prefilled turns: the SAME
    multi-turn conversations (greedy, so histories match bitwise)
    driven through an engine with sessions on vs a prefix-cache-off
    engine.  The warm lane's turn k+1 re-prefills only its new user
    tokens (session pin adoption); the cold lane recomputes the whole
    history every turn.  Compared on turn>=2 TTFT and on prefill
    tokens actually computed (the deterministic, timing-free
    separation the dry lane pins)."""
    import numpy as np

    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.serving import ServeConfig, ServeEngine

    vocab = model.config.vocab_size
    if dry:
        n_conv, n_turns, turn_new = 3, 3, 5
        user_len = (3, 6)
        mk = lambda pfx: ServeConfig(
            block_size=4, num_blocks=64, max_batch=n_conv,
            prefill_chunk=8, max_seq_len=model.config.max_seq_len,
            prefix_cache=pfx)
    else:
        n_conv, n_turns, turn_new = 8, 4, 16
        user_len = (8, 17)
        mk = lambda pfx: ServeConfig(
            block_size=8, num_blocks=256, max_batch=n_conv,
            prefill_chunk=16, max_seq_len=model.config.max_seq_len,
            prefix_cache=pfx)
    rng = np.random.RandomState(seed + 2)
    user_turns = [
        [rng.randint(0, vocab,
                     (int(rng.randint(*user_len)),)).tolist()
         for _ in range(n_turns)]
        for _ in range(n_conv)]

    lanes = {}
    # the dry session schedule matches the dry fleet schedule by
    # construction, so the campaign's warmed pair is reusable; the
    # real lanes size their own batch/blocks, so the first lane
    # compiles and the second adopts (prefix on/off shares programs —
    # the cache is host-side allocator state)
    lane_programs = programs if dry else None
    for name, pfx in (("session_warm", True), ("session_cold", False)):
        eng = ServeEngine(model, params, mk(pfx), programs=lane_programs)
        lane_programs = eng.programs
        snap = COUNTERS.snapshot()
        hist = [[] for _ in range(n_conv)]
        later_ttfts = []  # turn >= 2 only: the warm-vs-cold separation
        outputs = []
        print(f"--- session lane: {name} ({n_conv} conversations x "
              f"{n_turns} turns) ---")
        try:
            for t in range(n_turns):
                reqs = []
                for c in range(n_conv):
                    prompt = hist[c] + user_turns[c][t]
                    reqs.append(eng.submit(
                        prompt, turn_new,
                        session_id=(c if pfx else None)))
                eng.run()
                for c, r in enumerate(reqs):
                    assert r.state == "finished", \
                        (name, t, c, r.state, r.error)
                    hist[c] = list(r.prompt) + list(r.out)
                    outputs.append(list(r.out))
                    if t >= 1 and r.ttft_s is not None:
                        later_ttfts.append(r.ttft_s * 1000.0)
        finally:
            eng.close()
        delta = COUNTERS.delta_since(snap)
        lanes[name] = {
            "conversations": n_conv,
            "turns": n_turns,
            "turn2plus_ttft_ms": {
                "p50": round(_percentile(later_ttfts, 50), 2),
                "p99": round(_percentile(later_ttfts, 99), 2)},
            "prefill_tokens_computed":
                delta.get("serve.prefill_chunks", {}).get("bytes", 0),
            "prefix_hit_tokens":
                delta.get("kv.prefix_hit_tokens", {}).get("bytes", 0),
            "session_pins":
                delta.get("kv.session_pins", {}).get("calls", 0),
            "prefix_hit_rate": _hit_rate(delta),
            "counters": delta,
            "outputs": outputs,
        }
        print(f"    turn>=2 TTFT p50 "
              f"{lanes[name]['turn2plus_ttft_ms']['p50']} ms; "
              f"{lanes[name]['prefill_tokens_computed']} prefill tok "
              f"computed, {lanes[name]['prefix_hit_tokens']} skipped")
    # greedy + bitwise prefix cache -> identical conversations; every
    # downstream turn's prompt (history) is only comparable because of
    # this, so assert it before comparing any latency
    assert lanes["session_warm"]["outputs"] == \
        lanes["session_cold"]["outputs"], \
        "pinned sessions changed greedy tokens"
    warm, cold = lanes["session_warm"], lanes["session_cold"]
    comparison = {
        "warm_ttft_p50_ms": warm["turn2plus_ttft_ms"]["p50"],
        "cold_ttft_p50_ms": cold["turn2plus_ttft_ms"]["p50"],
        "warm_prefill_tokens": warm["prefill_tokens_computed"],
        "cold_prefill_tokens": cold["prefill_tokens_computed"],
        "session_pins": warm["session_pins"],
    }
    return lanes, comparison


def run_fleet_campaign(n_requests=64, rate_hz=32.0, seed=0, record=True,
                       dry=False, replica_counts=(1, 2, 4)):
    """The prefix-cache + fleet campaign: ONE shared-prefix greedy
    Poisson timeline replayed against (a) a prefix-cache-OFF single
    engine — simultaneously the cold baseline row and the bitwise
    oracle — and (b) a FleetRouter lane per replica count with
    per-replica prefix caches; plus the warm-session vs cold-turn
    lanes.  Headline claims: the cache serves > 50% of
    prefill tokens on this workload, warm session turns beat cold
    turns on turn>=2 TTFT p50, and tokens/s scales with replica
    count.  Every cache-on lane is asserted BITWISE identical to the
    cache-off oracle — the speed claim can never drift from the
    exactness contract."""
    import jax

    from deepspeed_tpu.serving import ServeConfig, ServeEngine

    if dry:
        n_requests = min(n_requests, 10)
        replica_counts = (1, 2)
        model, params = _nano_model(vocab=64, max_seq=64, d_model=32)
        vocab = 64
        mk = lambda pfx: ServeConfig(
            block_size=4, num_blocks=64, max_batch=3, prefill_chunk=8,
            max_seq_len=64, prefix_cache=pfx)
        timeline = build_prefix_timeline(
            n_requests, max(rate_hz, 48.0), seed, vocab, n_prefixes=2,
            prefix_len=12, tail_range=(2, 5), new_range=(4, 8))
    else:
        model, params = _nano_model(vocab=512, max_seq=256, layers=4,
                                    d_model=128, heads=8)
        vocab = 512
        mk = lambda pfx: ServeConfig(
            block_size=8, num_blocks=160, max_batch=4, prefill_chunk=16,
            max_seq_len=256, prefix_cache=pfx)
        timeline = build_prefix_timeline(
            n_requests, rate_hz, seed, vocab, n_prefixes=4,
            prefix_len=64, tail_range=(4, 16), new_range=(8, 32))

    warm = ServeEngine(model, params, mk(True))
    warm.generate([timeline[0][1]], 2)
    programs = warm.programs
    del warm

    print(f"--- fleet lane: cache off, 1 replica "
          f"({len(timeline)} requests, the bitwise oracle) ---")
    off, _eng = run_lane(model, params, mk(False), timeline,
                         programs=programs)
    _print_lane("cache_off_r1", off)
    lanes = {"cache_off_r1": off}
    scaling = {}
    for r in replica_counts:
        print(f"--- fleet lane: cache on, {r} replica(s) "
              f"({len(timeline)} requests, Poisson) ---")
        m = run_fleet_lane(model, params, mk(True), timeline, r,
                           programs=programs)
        # the exactness contract, asserted on the bench's own numbers:
        # greedy tokens with the cache on == cache off, bitwise
        assert m["outputs"] == off["outputs"], \
            f"prefix cache changed greedy tokens (replicas={r})"
        lanes[f"fleet_r{r}"] = m
        scaling[r] = m["tokens_per_sec"]
        _print_lane(f"fleet_r{r}", m)
        print(f"    prefix hit rate {m['prefix_hit_rate']:.1%} "
              f"({m['prefix_hit_tokens']} tok skipped, "
              f"{m['cow_copies']} COW); dispatches/replica "
              f"{m['dispatch_per_replica']}, spills {m['spills']}, "
              f"shed {m['shed_router']}")

    ses_lanes, session = run_session_lanes(model, params, seed=seed,
                                           dry=dry, programs=programs)
    lanes.update(ses_lanes)
    top = lanes[f"fleet_r{max(replica_counts)}"]
    if not dry:
        # committed-artifact floors (the ISSUE's acceptance numbers) —
        # asserted here so an artifact below them cannot be recorded
        assert top["prefix_hit_rate"] > 0.5, top["prefix_hit_rate"]
        assert session["warm_ttft_p50_ms"] < session["cold_ttft_p50_ms"], \
            session

    outputs = {name: m.pop("outputs") for name, m in lanes.items()}
    result = {
        "metric": "serve_fleet_bench",
        "platform": jax.default_backend(),
        "dry_run": dry,
        "n_requests": len(timeline),
        "rate_hz": rate_hz,
        "seed": seed,
        "model": {"layers": model.config.num_layers,
                  "d_model": model.config.d_model,
                  "heads": model.config.num_heads,
                  "vocab": model.config.vocab_size},
        "lanes": lanes,
        "replica_scaling_tokens_per_sec": scaling,
        "session": session,
        "prefix_hit_rate": top["prefix_hit_rate"],
        "value": top["prefix_hit_rate"],
        "unit": "prefix cache hit rate (fraction of prefill tokens)",
    }
    if record:
        result["artifact"], result["run_dir"] = record_serving(result)
        print(f"artifact: {result['artifact']}")
        print(f"report:   python tools/run_report.py {result['run_dir']}")
    result["outputs"] = outputs  # post-record: oracle material only
    return result


def run_dry_fleet(record=False):
    """Tier-1 CPU miniature of the fleet campaign
    (tests/test_serving.py): the shared-prefix timeline through the
    cache-off oracle, 1- and 2-replica fleets, and the session lanes.
    Pins the deterministic halves of every headline claim — bitwise
    cache-on == cache-off (asserted inside run_fleet_campaign), a
    nonzero cache hit rate, session pins engaging, and the warm lane
    computing STRICTLY fewer prefill tokens than the cold lane — and
    leaves the timing claims (TTFT separation, tok/s scaling) to the
    recorded campaign, where they belong."""
    result = run_fleet_campaign(record=record, dry=True)
    for name, lane in result["lanes"].items():
        if "requests" in lane:  # session lanes assert internally
            assert lane["completed"] == lane["requests"], (name, lane)
            assert lane["errored"] == 0, (name, lane)
    for r in (1, 2):
        lane = result["lanes"][f"fleet_r{r}"]
        assert lane["prefix_hit_rate"] > 0.25, (r, lane)
        assert lane["prefix_hits"] > 0, (r, lane)
        assert lane["shed_router"] == 0, (r, lane)
        assert sum(lane["dispatch_per_replica"]) == lane["requests"]
    assert result["lanes"]["cache_off_r1"]["prefix_hit_rate"] == 0.0
    ses = result["session"]
    assert ses["session_pins"] > 0, ses
    assert ses["warm_prefill_tokens"] < ses["cold_prefill_tokens"], ses
    return result


def record_serving(result):
    """Flat artifact via record_bench_result + a run directory holding
    serving.json for tools/run_report.py."""
    from deepspeed_tpu.monitor.artifacts import record_bench_result

    rel = record_bench_result(result)
    runs_root = os.path.join(os.path.dirname(HERE), "bench_artifacts",
                             "runs")
    stamp = os.path.basename(rel).rsplit(".", 1)[0]
    run_dir = os.path.join(runs_root, stamp)
    os.makedirs(run_dir, exist_ok=True)
    serving = {"schema_version": SERVING_SCHEMA_VERSION,
               "model": result["model"],
               "n_requests": result["n_requests"],
               "rate_hz": result["rate_hz"],
               "lanes": {name: {k: v for k, v in lane.items()
                                if k not in ("counters", "outputs")}
                         for name, lane in result["lanes"].items()}}
    with open(os.path.join(run_dir, "serving.json"), "w") as f:
        json.dump(serving, f, indent=2, sort_keys=True)
    return rel, os.path.relpath(run_dir, os.path.dirname(HERE))


def run_dry(record=False):
    """Tier-1 CPU miniature (tests/test_serving.py): both lanes finish
    every request, metrics are well-formed; no perf assertion — the
    point is that the lane cannot rot.  Runs the continuous lane
    TRACED so the per-request timeline (queue_wait -> prefill_chunk ->
    decode_step) and the SLO-window/bench percentile agreement are
    tier-1 pinned too."""
    import shutil

    result = run_campaign(record=record, dry=True, trace=True)
    for name, lane in result["lanes"].items():
        assert lane["completed"] == lane["requests"], (name, lane)
        assert lane["errored"] == 0, (name, lane)
        assert lane["tokens"] > 0 and lane["tokens_per_sec"], (name, lane)
        assert lane["ttft_ms"]["p99"] is not None, (name, lane)
        assert lane["kv_blocks"]["peak"] <= lane["kv_blocks"]["capacity"]
    assert result["lanes"]["continuous"]["tokens"] == \
        result["lanes"]["static"]["tokens"], \
        "both lanes decode the same timeline: token totals must agree"
    # the traced lane parsed: every request's lifecycle spans are there
    tr = result["trace"]
    try:
        from deepspeed_tpu.monitor.tracing import read_trace_file

        segments, summary = read_trace_file(
            os.path.join(tr["dir"], "trace.rank00000.jsonl"))
        events = [e for _meta, evs in segments for e in evs]
        names = {e["name"] for e in events}
        for want in ("queue_wait", "prefill_chunk", "decode_step",
                     "first_token", "finish"):
            assert want in names, (want, sorted(names))
        n_req = result["lanes"]["continuous"]["requests"]
        assert sum(1 for e in events if e["name"] == "queue_wait") \
            == n_req, "every request admits exactly once"
        assert summary is not None and summary["dropped"] == 0, summary
        assert tr["slo"]["requests"] == n_req, tr["slo"]
        assert tr["slo_events"], "no slo windows emitted"
    finally:
        if not record:
            shutil.rmtree(tr["dir"], ignore_errors=True)
    return result


def run_dry_spec(record=False):
    """Tier-1 CPU miniature of the speculative campaign
    (tests/test_spec_decode.py): sweep (kv_dtype x draft_len) on the
    shared repetitive timeline and pin the lanes to their oracles —

    * dense draft=0 lane == `generate()` bitwise (the serving engine
      IS the sequential decoder);
    * bf16 lanes == `generate(cache_dtype=bf16)` bitwise — the
      quantized-store analogue of the same pin;
    * every draft>0 lane == its draft=0 lane at matched kv_dtype
      (speculation changes WHEN tokens arrive, never WHICH), asserted
      inside run_spec_campaign for all kv_dtypes including int8/int4;
    * draft>0 lanes actually speculate (accepted_tokens > 0) and the
      resident-session pair actually separates (int8 > bf16)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.generation import generate

    result = run_spec_campaign(record=record, dry=True,
                               kv_dtypes=(None, "bf16", "int8", "int4"),
                               draft_lens=(0, 2))
    lanes, outputs = result["lanes"], result["outputs"]
    for name, lane in lanes.items():
        assert lane["completed"] == lane["requests"], (name, lane)
        assert lane["errored"] == 0 and lane["shed"] == 0, (name, lane)
        if lane["draft_len"] > 0:
            assert lane["accepted_tokens"] > 0, \
                (name, "repetitive greedy lane accepted no drafts")
            assert lane["accepted_per_step"] > 0, (name, lane)
    # bitwise pins against the no-serving-engine oracle
    model, params = _nano_model(vocab=64, max_seq=64, d_model=32)
    timeline = build_spec_timeline(result["n_requests"], 8.0,
                                   result["seed"], 64,
                                   pattern_range=(4, 5), repeats=3,
                                   new_range=(10, 11))
    for lane_name, cache_dtype in (("dense_d0", None),
                                   ("bf16_d0", jnp.bfloat16),
                                   ("bf16_d2", jnp.bfloat16)):
        oracle = [generate(model, params, jnp.asarray([prompt]), max_new,
                           cache_dtype=cache_dtype)[0].tolist()
                  for _t, prompt, max_new, _T, _k, _s in timeline]
        assert outputs[lane_name] == oracle, \
            f"{lane_name} diverged from generate()"
    assert result["resident_sessions"]["resident_ratio"] > 1.0, \
        result["resident_sessions"]
    return result


def run_dry_chaos(record=False):
    """Chaos lane (tier-1 via tests/test_serving.py): hang one decode
    step -> StepWatchdog trips -> the wedged batch is SHED (state
    'error', blocks reclaimed) -> everything waiting completes with
    oracle-identical output."""
    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.runtime.resilience import (FaultPlan, FaultRule,
                                                  StepWatchdog,
                                                  install_fault_plan)
    from deepspeed_tpu.serving import ServeConfig, ServeEngine

    model, params = _nano_model(vocab=64, max_seq=64, d_model=32)
    cfg = ServeConfig(block_size=4, num_blocks=48, max_batch=2,
                      prefill_chunk=8, max_seq_len=64)
    import numpy as np

    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 64, (n,)).tolist() for n in (5, 7, 4, 6)]

    # oracle: every request alone, no faults
    oracle_eng = ServeEngine(model, params, cfg)
    oracle = [oracle_eng.generate([p], 6)[0] for p in prompts]

    import tempfile

    with tempfile.TemporaryDirectory() as snap_dir:
        eng = ServeEngine(model, params, cfg, programs=oracle_eng.programs)
        wd = StepWatchdog(deadline_s=0.5, snapshot_dir=snap_dir,
                          poll_s=0.05,
                          on_trip=lambda trip: eng.request_shed(
                              trip["reason"]))
        eng.attach_watchdog(wd)
        # two requests running, then the 3rd decode call hangs past the
        # watchdog deadline
        plan = FaultPlan([FaultRule(site="serve.decode", kind="hang",
                                    hang_s=1.5, calls=[2])], seed=0)
        install_fault_plan(plan)
        snap = COUNTERS.snapshot()
        try:
            r01 = [eng.submit(prompts[0], 6), eng.submit(prompts[1], 6)]
            while any(not r.done for r in r01):
                eng.step()
            r23 = [eng.submit(prompts[2], 6), eng.submit(prompts[3], 6)]
            eng.run()
        finally:
            install_fault_plan(None)
            eng.close()
            wd.stop()
        delta = COUNTERS.delta_since(snap)

    shed = [r for r in r01 if r.state == "error"]
    assert len(shed) == 2, [r.state for r in r01]
    assert wd.trips == 1, wd.trips
    assert delta.get("serve.shed", {}).get("calls") == 2, delta
    assert delta.get("kv.evictions", {}).get("calls", 0) > 0, delta
    assert delta.get("fault.injected", {}).get("calls") == 1, delta
    # the batch behind the wedge completes, token-identical
    assert [r.out for r in r23] == oracle[2:], \
        (oracle[2:], [r.out for r in r23])
    assert eng.kv.blocks_in_use == 0
    result = {"metric": "serve_chaos", "shed": len(shed),
              "watchdog_trips": wd.trips,
              "survivors_ok": [r.out for r in r23] == oracle[2:]}
    if record:
        from deepspeed_tpu.monitor.artifacts import record_bench_result

        result["artifact"] = record_bench_result(result)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="seconds-scale miniature (the tier-1 lane)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding campaign: (kv_dtype x "
                    "draft_len) lanes + equal-pool resident sessions")
    ap.add_argument("--fleet", action="store_true",
                    help="prefix-cache + fleet campaign: shared-prefix "
                    "Poisson traffic through the cache-off oracle and "
                    "1/2/4-replica routed fleets, plus warm-session vs "
                    "cold-turn lanes")
    ap.add_argument("--replicas", type=int, nargs="+",
                    default=(1, 2, 4),
                    help="replica counts for the --fleet lanes")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate (req/s); default 24 for "
                    "the batching campaign, 64 for --spec (the spec "
                    "lanes measure a saturated single-slot queue)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-record", action="store_true")
    ap.add_argument("--trace", action="store_true",
                    help="attach a TraceRecorder + ServingSLO to the "
                    "continuous lane: per-request timeline + SLO "
                    "windows land beside serving.json in the run dir")
    args = ap.parse_args()
    if args.dry_run and args.fleet:
        run_dry_fleet(record=not args.no_record)
        print("serve_bench fleet dry-run ok")
        return 0
    if args.dry_run and args.spec:
        run_dry_spec(record=not args.no_record)
        print("serve_bench spec dry-run ok")
        return 0
    if args.fleet:
        result = run_fleet_campaign(
            n_requests=args.requests, rate_hz=args.rate or 32.0,
            seed=args.seed, record=not args.no_record,
            replica_counts=tuple(args.replicas))
        print(f"\nprefix cache hit rate (largest fleet): "
              f"{result['prefix_hit_rate']:.1%}")
        print(f"tokens/s vs replicas: "
              f"{result['replica_scaling_tokens_per_sec']}")
        print(f"warm vs cold turn>=2 TTFT p50: "
              f"{result['session']['warm_ttft_p50_ms']} vs "
              f"{result['session']['cold_ttft_p50_ms']} ms")
        return 0
    if args.dry_run:
        run_dry(record=not args.no_record)
        print("serve_bench dry-run ok")
        return 0
    if args.spec:
        result = run_spec_campaign(n_requests=min(args.requests, 32),
                                   rate_hz=args.rate or 64.0,
                                   seed=args.seed,
                                   record=not args.no_record)
        print(f"\nspec speedup (tokens/s, draft=4 vs draft=0): "
              f"{result['spec_speedup_tokens_per_sec']}")
        print(f"resident sessions at equal pool bytes: "
              f"{result['resident_sessions']}")
        return 0
    result = run_campaign(n_requests=args.requests,
                          rate_hz=args.rate or 24.0,
                          seed=args.seed, record=not args.no_record,
                          trace=args.trace)
    cont = result["lanes"]["continuous"]
    stat = result["lanes"]["static"]
    print(f"\ncontinuous vs static: "
          f"{cont['tokens_per_sec']} vs {stat['tokens_per_sec']} tok/s "
          f"({result['speedup_tokens_per_sec']}x), TTFT p99 "
          f"{cont['ttft_ms']['p99']} vs {stat['ttft_ms']['p99']} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
