#!/usr/bin/env python
"""Render a telemetry run (monitor/ JSONL event stream) as a
markdown report.

Usage:
    python tools/run_report.py runs/my_run            # a run directory
    python tools/run_report.py runs/my_run -o rep.md  # write to a file
    python tools/run_report.py --selftest             # synthetic round-trip

The run directory is what `{"monitor": {"enabled": true}}` produces:
manifest.json + events.rank*.jsonl (+ summaries).  `--selftest` writes a
synthetic run through the real writer and renders it back — a smoke for
the whole schema path with no engine involved.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def selftest() -> int:
    import tempfile

    from deepspeed_tpu.monitor import (COUNTERS, DeepSpeedMonitorConfig,
                                       RunMonitor)
    from deepspeed_tpu.monitor.report import (load_run, render_markdown,
                                              summarize, validate_event)

    with tempfile.TemporaryDirectory() as root:
        cfg = DeepSpeedMonitorConfig({"monitor": {
            "enabled": True, "output_path": root, "job_name": "selftest",
            "flush_interval": 1, "tokens_per_sample": 128}})
        mon = RunMonitor(cfg, rank=0, world=1)
        for step in range(1, 4):
            mon.step_start(step - 1)
            COUNTERS.add("p2p.send", 1024)
            # hierarchical grad-wire levels: fast-fabric legs + the
            # slow-fabric shard hop (report renders them as their own
            # per-level section)
            COUNTERS.add("grad_wire.intra", 8192, calls=2)
            COUNTERS.add("grad_wire.inter", 1024, calls=1)
            # comm/compute overlap: exposed wire µs (the ckpt.stall_ms
            # µs-in-bytes convention) + qwZ prefetch hits — rendered in
            # the gradient-wire section, excluded from the byte table
            COUNTERS.add("grad_wire.exposed_ms", 850, calls=1)
            COUNTERS.add("qwz.prefetch_hits", 4200, calls=1)
            # input pipeline: host wait (µs in the bytes slot), H2D
            # payload, prefetch queue occupancy — rendered as their own
            # "Input pipeline" section, not comm rows
            COUNTERS.add("input.host_wait_ms", 1500, calls=1)
            COUNTERS.add("input.h2d_bytes", 4096, calls=2)
            COUNTERS.add("input.queue_depth", 2, calls=1)
            # resilience: injected faults absorbed by retry/respawn +
            # a watchdog trip — rendered as the "Resilience" section
            COUNTERS.add("fault.injected", calls=1)
            COUNTERS.add("fault.retried", calls=2)
            COUNTERS.add("fault.recovered_ms", 2500, calls=1)
            COUNTERS.add("watchdog.trips", calls=1)
            COUNTERS.add("input.worker_respawns", calls=1)
            # overlap-exchange self-healing: healed drops, replayed
            # frames (bytes = replayed payload), a demotion — all
            # Resilience rows, never comm byte rows
            COUNTERS.add("exchange.reconnects", calls=1)
            COUNTERS.add("exchange.resends", 2048, calls=1)
            COUNTERS.add("exchange.demotions", calls=1)
            # elastic world-size transitions consumed on restore —
            # Resilience rows, excluded from the comm byte table
            COUNTERS.add("elastic.shrinks", calls=1)
            COUNTERS.add("elastic.regrows", calls=1)
            # serving engine (deepspeed_tpu/serving): rendered as the
            # "Serving" section, never comm byte rows; serve.ttft_ms
            # carries µs in the bytes slot, kv.blocks_in_use is an
            # occupancy sample (mean = bytes/calls)
            COUNTERS.add("serve.requests", 24, calls=2)
            COUNTERS.add("serve.tokens", calls=12)
            COUNTERS.add("serve.decode_steps", 9, calls=3)
            COUNTERS.add("serve.prefill_chunks", 16, calls=2)
            COUNTERS.add("serve.ttft_ms", 250_000, calls=2)
            COUNTERS.add("serve.shed", calls=1)
            COUNTERS.add("kv.blocks_in_use", 10, calls=4)
            COUNTERS.add("kv.evictions", calls=3)
            # speculative decoding over a quantized cache: proposed vs
            # accepted drafts + decode dispatch wall µs against the
            # quantized store (kv.dequant_ms is µs-in-bytes) — rendered
            # as the Serving section's "Speculative decoding" rows
            COUNTERS.add("serve.draft_tokens", calls=8)
            COUNTERS.add("serve.accepted_tokens", calls=6)
            COUNTERS.add("kv.dequant_ms", 90_000, calls=3)
            # block-level prefix caching + pinned sessions: hit
            # admissions (bytes = blocks aliased), prompt tokens whose
            # prefill was skipped, COW privatizations (bytes = device
            # bytes copied), session pins (bytes = blocks held), LRU
            # reclaims — the Serving section's "Prefix cache" rows;
            # router.* (fleet dispatch/spill/shed) is the "Fleet
            # router" section.  All excluded from the comm byte table.
            COUNTERS.add("kv.prefix_hits", 4, calls=2)
            COUNTERS.add("kv.prefix_hit_tokens", 16, calls=2)
            COUNTERS.add("kv.cow_copies", 4608, calls=1)
            COUNTERS.add("kv.session_pins", 6, calls=2)
            COUNTERS.add("kv.prefix_evictions", calls=1)
            COUNTERS.add("router.dispatches", 5, calls=2)
            COUNTERS.add("router.spills", calls=1)
            COUNTERS.add("router.shed", calls=1)
            # MoE wire (moe/dispatch.py): a2a hop bytes + the
            # slow-fabric subset, exposed µs (ckpt.stall_ms
            # convention), capacity drops and ppm-in-bytes bucket
            # occupancy — the "MoE wire" section, never comm byte rows
            COUNTERS.add("moe.a2a_bytes", 65536, calls=4)
            COUNTERS.add("moe.a2a_inter", 16384, calls=2)
            COUNTERS.add("moe.a2a_exposed_ms", 1200, calls=1)
            COUNTERS.add("moe.dropped_tokens", 5, calls=2)
            COUNTERS.add("moe.capacity_frac", 750_000, calls=1)
            # the self-tuning runtime (runtime/autotune/): probe µs in
            # the bytes slot, cache/swap/retune counts — rendered as
            # the "Autotune" section, never comm byte rows
            COUNTERS.add("autotune.probes", 420_000, calls=3)
            COUNTERS.add("autotune.cache_hits", calls=1)
            COUNTERS.add("autotune.rejected", calls=2)
            COUNTERS.add("autotune.retunes", calls=1)
            COUNTERS.add("autotune.swaps", calls=1)
            # the Pallas kernel registry (deepspeed_tpu/kernels):
            # trace-time dispatch resolutions — rendered as the
            # "Kernels" section, never comm byte rows
            COUNTERS.add("kernel.dispatches", calls=4)
            COUNTERS.add("kernel.fallbacks", calls=2)
            # trace recorder bookkeeping (monitor/tracing.py): event/
            # byte tallies + SLO window count — rendered as the
            # "Serving SLO" section's Tracing rows, never comm byte rows
            COUNTERS.add("trace.events", 2048, calls=12)
            COUNTERS.add("trace.dropped", calls=1)
            COUNTERS.add("slo.windows", calls=1)
            sp = mon.span("forward")
            sp.close()
            mon.step_end(step, loss=4.0 / step, lr=1e-3, loss_scale=1.0,
                         samples_per_sec=100.0, skipped_steps=0,
                         pipe={"occupancy": [
                             {"stage": 0, "ticks": 9, "compute_ticks": 8,
                              "bubble_frac": 0.1111}]})
        # live SLO windows (monitor.tracing.ServingSLO snapshots) land
        # in the event stream as type="slo" events and render as the
        # "Serving SLO" section; the report keeps the LAST window plus
        # the worst p99 seen across windows
        for p99 in (41.5, 55.0):
            mon.emit("slo", {"slo": {
                "window_s": 10.0, "requests": 6,
                "ttft_ms": {"p50": 21.0, "p99": p99, "n": 6},
                "tok_per_s": 180.0, "queue_depth_mean": 1.5,
                "accept_rate": 0.75, "drafted": 16, "shed": 1}})
        mon.close()
        # a supervisor restart ledger beside the event streams
        # (elasticity/supervisor.py) renders as the "Restarts" section
        import json as _json

        with open(os.path.join(root, "selftest", "restarts.jsonl"),
                  "w") as f:
            f.write(_json.dumps({
                "t": 0.0, "event": "restart", "attempt": 1,
                "ran_for_s": 12.5, "exit_code": -15,
                "reason": "watchdog trip on rank 0: step deadline",
                "dead_ranks": [], "backoff_s": 5.0,
                "diagnostics": "watchdog_snapshot.rank00000.1.json",
            }) + "\n")
            # an elastic shrink + regrow pair (supervisor
            # --elastic-shrink) renders as the "Elastic transitions"
            # block beside the Restarts table
            f.write(_json.dumps({
                "t": 1.0, "event": "restart", "attempt": 2,
                "ran_for_s": 33.0, "exit_code": 1,
                "reason": "rank(s) [3] went quiet first",
                "dead_ranks": [3], "backoff_s": 5.0,
                "from_world": 4, "to_world": 3, "transition": "shrink",
                "incarnation": 2,
            }) + "\n")
            f.write(_json.dumps({
                "t": 2.0, "event": "restart", "attempt": 3,
                "ran_for_s": 60.0, "exit_code": 1,
                "reason": "exit code 1",
                "dead_ranks": [], "backoff_s": 5.0,
                "from_world": 3, "to_world": 4, "transition": "regrow",
                "incarnation": 3,
            }) + "\n")
        # an autotune ledger beside the event streams (runtime/
        # autotune/runtime.py) renders as the "Autotune" event table
        with open(os.path.join(root, "selftest", "autotune.jsonl"),
                  "w") as f:
            f.write(_json.dumps({
                "t": 0.0, "event": "search", "step": 1, "probes": 3,
                "baseline_ms": 12.5, "fingerprint": "abcd1234",
            }) + "\n")
            f.write(_json.dumps({
                "t": 1.0, "event": "retune", "step": 2,
                "reason": "step time regression: 30.0 ms/step > 1.50 x "
                          "baseline 12.5 ms",
                "incumbent": "flat_fp32_overlap", "probes": 2,
                "swapped": True, "winner": "flat_fp32",
            }) + "\n")
            f.write(_json.dumps({
                "t": 1.5, "event": "swap", "step": 2,
                "candidate": "flat_fp32",
                "reason": "online retune: exposed wire creep",
            }) + "\n")
        # a serving-bench lane table (tools/serve_bench.py serving.json)
        # renders as the "Serving bench" table beside the training
        # sections
        with open(os.path.join(root, "selftest", "serving.json"),
                  "w") as f:
            lane = lambda tps, p99: {
                "requests": 8, "completed": 8, "errored": 0,
                "tokens": 96, "tokens_per_sec": tps, "makespan_s": 1.0,
                "ttft_ms": {"p50": 12.0, "p99": p99, "mean": 20.0},
                "itl_ms": {"p50": 2.0, "p99": 6.0},
                "kv_blocks": {"mean": 9.5, "peak": 14, "capacity": 31},
                "shed": 0}
            spec_lane = dict(lane(165.0, 35.0), accepted_per_step=1.8,
                             kv_dtype="int8", draft_len=4)
            _json.dump({"schema_version": 1, "n_requests": 8,
                        "rate_hz": 4.0,
                        "model": {"layers": 2, "d_model": 32, "heads": 4,
                                  "vocab": 64},
                        "lanes": {"continuous": lane(120.0, 40.0),
                                  "static": lane(80.0, 90.0),
                                  "spec_int8_d4": spec_lane}}, f)
        run = load_run(os.path.join(root, "selftest"))
        bad = [err for events in run["ranks"].values()
               for e in events for err in validate_event(e)]
        assert not bad, f"schema violations: {bad}"
        s = summarize(run["ranks"][0])
        assert s["n_steps"] == 3, s
        assert s["comm"]["p2p.send"]["bytes"] == 3072, s
        assert s["mean_tokens_per_sec"] is not None, s
        md = render_markdown(run)
        for needle in ("Run report", "p2p.send", "Pipeline occupancy",
                       "11.1%", "forward", "Gradient wire levels",
                       "inter-group", "slow-fabric share",
                       "Input pipeline", "host wait", "H2D batch transfer",
                       "mean prefetch queue depth",
                       "Resilience", "faults injected", "transient retries",
                       "watchdog trips", "prefetch workers respawned",
                       "exchange connections healed",
                       "exchange frames resent", "6,144 B replayed",
                       "demotions to the serial path",
                       "Restarts (supervisor ledger)", "watchdog trip on "
                       "rank 0",
                       "Elastic transitions", "shrink | 4 → 3",
                       "regrow | 3 → 4",
                       "elastic shrinks (resumed at a smaller dp)",
                       "elastic regrows (resumed at a larger dp)",
                       "## Serving", "requests completed",
                       "mean batch occupancy", "mean time-to-first-token",
                       "mean KV blocks in use",
                       "KV blocks force-reclaimed",
                       "requests shed (wedged decode)",
                       "**Speculative decoding**",
                       "draft tokens proposed | 24 (75% accepted)",
                       "draft tokens accepted | 18 (+2.00 bonus "
                       "tokens/step)",
                       "quantized-KV decode dispatch",
                       "**Prefix cache**",
                       "prefix-hit admissions | 6 (12 blocks aliased)",
                       "prompt tokens skipped | 48 (50% of prefill "
                       "tokens)",
                       "copy-on-write privatizations | 3 "
                       "(13.50 KiB copied)",
                       "session pins | 6 (18 blocks held)",
                       "cached blocks reclaimed (LRU) | 3",
                       "## Fleet router",
                       "requests dispatched | 6 (mean load at dispatch "
                       "2.50 KV blocks)",
                       "queue spill-overs | 3",
                       "requests shed at front door | 3",
                       "Serving bench (continuous batching)",
                       "Speculative decoding lanes",
                       "spec_int8_d4: +1.80 tok/step (kv int8, draft 4)",
                       "continuous vs static batching: 1.50x",
                       "MoE wire (expert all-to-all)",
                       "a2a wire bytes", "slow-fabric (inter-group) share",
                       "exposed a2a time", "tokens dropped at capacity",
                       "mean expert-bucket utilisation | 75.0%",
                       "## Autotune", "candidate probes",
                       "winner-cache hits (zero probes)",
                       "candidates pruned by config validators",
                       "online retunes (sustained regression)",
                       "live config swaps applied",
                       "swapped to `flat_fp32`",
                       "online retune: exposed wire creep",
                       "## Kernels",
                       "Pallas kernel dispatches (trace-time) | 12",
                       "jnp oracle fallbacks (trace-time) | 6",
                       "## Serving SLO", "SLO windows emitted | 2",
                       "last window: TTFT p50/p99 | 21.00 / 55.00 ms "
                       "(n=6)",
                       "last window: decode throughput | 180.00 tokens/s",
                       "last window: mean admission queue depth | 1.50",
                       "last window: draft accept rate | 75.0% "
                       "(16 drafted)",
                       "last window: requests shed | 1",
                       "worst window TTFT p99 | 55.00 ms",
                       "**Tracing**", "trace events recorded | 36",
                       "trace events dropped (byte cap) | 3",
                       "SLO windows aggregated | 3"):
            assert needle in md, f"{needle!r} missing from report"
        assert "`input.host_wait_ms`" not in md, \
            "input.* rows must not leak into the comm table"
        assert "`grad_wire.exposed_ms`" not in md and \
            "`qwz.prefetch_hits`" not in md, \
            "µs-convention wire counters must not leak into the comm table"
        assert "`fault.injected`" not in md and \
            "`watchdog.trips`" not in md, \
            "fault.*/watchdog.* rows must not leak into the comm table"
        assert "`exchange.reconnects`" not in md and \
            "`exchange.resends`" not in md, \
            "exchange.* rows must not leak into the comm table"
        assert "`elastic.shrinks`" not in md and \
            "`elastic.regrows`" not in md, \
            "elastic.* rows must not leak into the comm table"
        assert "`serve.tokens`" not in md and \
            "`kv.blocks_in_use`" not in md and \
            "`serve.draft_tokens`" not in md and \
            "`serve.accepted_tokens`" not in md and \
            "`kv.dequant_ms`" not in md, \
            "serve.*/kv.* rows must not leak into the comm table"
        assert "`kv.prefix_hits`" not in md and \
            "`kv.prefix_hit_tokens`" not in md and \
            "`kv.cow_copies`" not in md and \
            "`kv.session_pins`" not in md and \
            "`kv.prefix_evictions`" not in md and \
            "`router.dispatches`" not in md and \
            "`router.spills`" not in md and \
            "`router.shed`" not in md, \
            "kv.*/router.* rows must not leak into the comm table"
        assert "`moe.a2a_bytes`" not in md and \
            "`moe.capacity_frac`" not in md, \
            "moe.* rows must not leak into the comm table"
        assert "`autotune.probes`" not in md and \
            "`autotune.swaps`" not in md, \
            "autotune.* rows must not leak into the comm table"
        assert "`kernel.dispatches`" not in md and \
            "`kernel.fallbacks`" not in md, \
            "kernel.* rows must not leak into the comm table"
        assert "`trace.events`" not in md and \
            "`trace.dropped`" not in md and \
            "`slo.windows`" not in md, \
            "trace.*/slo.* rows must not leak into the comm table"
        # serving.json alone must render without event streams (the
        # serve-bench run-dir shape)
        import shutil as _shutil

        sv_dir = os.path.join(root, "sv_only")
        os.makedirs(sv_dir)
        _shutil.copy(os.path.join(root, "selftest", "serving.json"),
                     sv_dir)
        md2 = render_markdown(load_run(sv_dir))
        assert "Serving bench (continuous batching)" in md2, md2
    print("run_report selftest ok")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir", nargs="?",
                    help="run directory (manifest.json + events.rank*.jsonl)")
    ap.add_argument("-o", "--output", help="write markdown here "
                    "(default: stdout)")
    ap.add_argument("--selftest", action="store_true",
                    help="synthetic write->render round-trip")
    args = ap.parse_args()
    if args.selftest:
        return selftest()
    if not args.run_dir:
        ap.error("run_dir is required (or --selftest)")

    from deepspeed_tpu.monitor.report import load_run, render_markdown

    md = render_markdown(load_run(args.run_dir))
    if args.output:
        with open(args.output, "w") as f:
            f.write(md)
        print(f"wrote {args.output}")
    else:
        print(md)
    return 0


if __name__ == "__main__":
    sys.exit(main())
