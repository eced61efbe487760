"""Autotune bench: the self-tuning runtime measured end to end.

Three lanes:

* `run_dry` (tier-1, CPU, in-process) — the SEARCH machinery on a
  seeded synthetic cost surface: the driver converges on the argmin of
  the surface, the winner is pinned deterministic for a fixed seed,
  the fingerprint cache round-trips (hit = zero probes, changed
  fingerprint = loud miss), and a zero-budget driver skips everything
  without caching.  Plus a small REAL-engine search through
  `engine.autotune_search` so the live probe/swap path can't rot.

* `--nproc 2` SEARCH lane (slow marker) — two jax.distributed
  processes on localhost TCP, the fabric where the wire rounds were
  measured.  An engine-factory probe (fresh engine per candidate, so
  mesh-layout knobs like `comm.hierarchy` participate) searches the
  legal space starting from the naive default (implicit flat fp32
  wire, no overlap) and must land within 10% of the hand-tuned
  BENCH round-13..17 recipe (hierarchical int8 outer hop + overlap),
  which sits IN the enumerated space — the search trace and winner are
  recorded as the committed artifact.

* `--nproc 2` RETUNE lane (same run) — an engine on the numerics-safe
  overlapped fp32 wire trains with `autotune.online` armed; a fault
  rule injects a wire slowdown (`exchange.send` delay) mid-run.  The
  sustained-regression detector must trigger EXACTLY ONE online
  retune, the swap lands on the serial wire, and the loss stream stays
  BITWISE equal to a serial-wire oracle run — the parity contract of
  safe-only online swaps.

Usage: python tools/autotune_bench.py [--nproc 2] [--steps 4]
           [--size nano] [--seq 32]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

SURFACE_BASE_MS = 120.0
# relative wire cost of the slow hop on a serialization-bound fabric
# (shaped after the measured BENCH rounds 7/8/11/13 ratios)
_WIRE_FACTOR = {"fp32": 1.0, "bf16": 0.72, "split": 0.85,
                "int8": 0.58, "int4": 0.52}


def synthetic_cost_ms(candidate, seed: int = 0,
                      base: float = SURFACE_BASE_MS) -> float:
    """Deterministic seeded cost surface over the candidate space,
    shaped like the measured TCP-fabric results: bucketing ~2x,
    hierarchy keeps the inner hop on the fast fabric, compressed slow
    hops win proportionally, overlap hides the wire when the exchanged
    payload is compressed/hierarchical and LOSES on the flat fp32 wire
    (the round-13 counterexample)."""
    import random

    k = candidate.knobs()
    cost = base
    if k["gradient_reduction"] == "bucketed":
        cost *= 0.5
        hier = k["hierarchy"] not in ("none", None, 1)
        slow = (k["wire_dtype_outer"] or k["wire_dtype"]) if hier \
            else k["wire_dtype"]
        if hier:
            cost *= 0.75 * (1.0 + 0.01 * int(k["hierarchy"]))
        cost *= _WIRE_FACTOR.get(slow, 1.0)
        if k["overlap"] == "on":
            compressed = hier or slow in ("bf16", "int8", "int4")
            cost *= 0.55 if compressed else 1.25
    rng = random.Random(f"{seed}:{candidate.name}")
    return cost * rng.uniform(0.97, 1.03)


def _surface_probe(seed: int):
    def probe(candidate):
        return {"step_ms": synthetic_cost_ms(candidate, seed=seed)}

    return probe


def run_dry(artifact_root: str, seed: int = 0) -> dict:
    """Tier-1 CPU lane (the grad_wire_bench.run_dry pattern).  Returns
    the recorded result dict; every contract violation asserts."""
    from deepspeed_tpu.runtime.autotune import (SearchDriver, WinnerCache,
                                                generate_candidates,
                                                make_fingerprint)

    cands, rejected = generate_candidates(
        dp=8, stage=0, wire_dtypes=("fp32", "bf16", "int8", "int4"),
        inner_dtypes=(None, "int8"))
    # the validators pruned something (e.g. the int8 inner wire on the
    # scatter level) — the tentpole's prune-before-probe contract
    assert rejected > 0, "expected the config validators to prune"

    # 1. convergence: exhaustive search == argmin of the surface, and
    #    the winner is deterministic for the seed
    expected = min(cands,
                   key=lambda c: synthetic_cost_ms(c, seed=seed)).name
    d1 = SearchDriver(_surface_probe(seed))
    best1 = d1.search(cands)
    d2 = SearchDriver(_surface_probe(seed))
    best2 = d2.search(cands)
    assert best1.candidate.name == best2.candidate.name == expected, \
        (best1.candidate.name, best2.candidate.name, expected)
    assert d1.complete and len(d1.results) == len(cands)

    # 2. fingerprint cache: hit returns the winner with zero probing;
    #    a changed fingerprint (mesh/world/dtype) is a loud miss
    fp = make_fingerprint(surface={"seed": seed, "base": SURFACE_BASE_MS},
                          mesh={"dp": 8, "data_outer": 1},
                          fabric={"topology": "synthetic"})
    cache_path = os.path.join(artifact_root, "autotune_dry_cache.json")
    cache = WinnerCache(cache_path)
    cache.store(fp, {"name": best1.candidate.name}, d1.trace())
    hit = cache.lookup(fp)
    assert hit is not None and hit["winner"]["name"] == expected
    fp2 = make_fingerprint(surface={"seed": seed, "base": SURFACE_BASE_MS},
                           mesh={"dp": 4, "data_outer": 2},
                           fabric={"topology": "synthetic"})
    assert cache.lookup(fp2) is None, \
        "a changed mesh fingerprint must never reuse the cached winner"

    # 3. budget: a zero-budget driver skips every candidate and the
    #    degraded outcome is not cacheable
    d3 = SearchDriver(_surface_probe(seed), budget_s=0.0)
    assert d3.search(cands) is None
    assert not d3.complete
    assert all(r.skipped == "budget" for r in d3.results)

    # 4. the REAL engine path: a small live search over three flat
    #    candidates through engine.autotune_search (probe -> decide ->
    #    swap), then a second search hitting the winner cache with
    #    ZERO probes
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config

    dp = jax.device_count()
    model_cfg = gpt2_config("nano", vocab_size=512, max_seq_len=16,
                            dropout=0.0, embed_dropout=0.0)
    engine_cache = os.path.join(artifact_root, "autotune_engine_cache.json")

    def build():
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT(model_cfg), dist_init_required=False,
            config_params={
                "train_batch_size": dp,
                "zero_optimization": {"stage": 0},
                "mesh": {"data": dp}, "steps_per_print": 0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "autotune": {"enabled": True, "probe_steps": 1,
                             "probe_warmup": 1,
                             "cache_path": engine_cache},
            })
        return engine

    rng = np.random.RandomState(0)
    tok = rng.randint(0, 512, (dp, 17)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])
    from deepspeed_tpu.runtime.autotune.space import generate_candidates \
        as gen

    live, _ = gen(dp=dp, stage=0, wire_dtypes=("fp32", "bf16"),
                  outers=(), overlap=(False,))
    engine = build()
    engine.forward(batch)
    engine.backward()
    engine.step()
    out = engine.autotune_search(candidates=live)
    assert not out["cached"] and out["probes"] == len(live), out
    engine.close_overlap()
    del engine
    gc.collect()
    engine2 = build()
    engine2.forward(batch)
    engine2.backward()
    engine2.step()
    out2 = engine2.autotune_search()
    assert out2["cached"] and out2["probes"] == 0, out2
    assert out2["winner"] == out["winner"], (out2["winner"], out["winner"])
    engine2.close_overlap()
    del engine2
    gc.collect()

    from deepspeed_tpu.monitor.artifacts import record_bench_result

    result = {
        "metric": "autotune_cpu_dryrun",
        "platform": "cpu",
        "world": {"processes": 1, "devices": dp},
        "value": len(cands),
        "unit": "legal_candidates",
        "synthetic": {"candidates": len(cands), "rejected": rejected,
                      "winner": expected,
                      "winner_ms": round(best1.metrics["step_ms"], 2),
                      "trace": d1.trace()},
        "engine": {"winner": out["winner"], "probes": out["probes"],
                   "baseline_ms": out["baseline_ms"],
                   "cached_second_search": bool(out2["cached"])},
    }
    result["artifact"] = record_bench_result(result, root=artifact_root)
    return result


# ---------------------------------------------------------------------------
# the 2-process TCP lanes
# ---------------------------------------------------------------------------


def _make_batches(dp: int, seq: int, n: int, vocab: int = 512):
    """Identical batch stream on every process (grad_wire_bench's
    discipline: device_put treats each process's value as the global
    array)."""
    import numpy as np

    rng = np.random.RandomState(0)
    out = []
    for _ in range(n):
        tok = rng.randint(0, vocab, (dp, seq + 1)).astype(np.int32)
        out.append((tok[:, :-1], tok[:, 1:]))
    return out


def _engine_probe_factory(model_cfg, dp: int, gas: int, steps: int,
                          warmup: int, batches):
    """Fresh engine per candidate: the rebuild-scope search (mesh-layout
    knobs like comm.hierarchy probe here, where initialize() can build
    the factored mesh the candidate asks for)."""
    import jax  # noqa: F401

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT

    def probe(cand):
        import numpy as np

        cfg = {
            "train_batch_size": dp * gas,
            "zero_optimization": {"stage": cand.stage},
            "mesh": {"data": dp}, "steps_per_print": 0,
            "optimizer": {"type": "Adam",
                          "params": {"lr": 1e-4, "weight_decay": 0.0}},
            "comm": dict(cand.comm),
        }
        if gas > 1:
            cfg["train_micro_batch_size_per_gpu"] = 1
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT(model_cfg), dist_init_required=False,
            config_params=cfg)
        try:
            for _ in range(warmup):
                for _m in range(gas):
                    engine.forward(batches[0])
                    engine.backward()
                engine.step()
            t = []
            for i in range(steps):
                t0 = time.perf_counter()
                for _m in range(gas):
                    loss = engine.forward(batches[0])
                    engine.backward()
                engine.step()
                loss.block_until_ready()
                t.append(time.perf_counter() - t0)
            return {"step_ms": round(float(np.median(t)) * 1e3, 2),
                    "loss": round(float(loss), 4)}
        finally:
            engine.close_overlap()
            del engine
            gc.collect()

    return probe


def _search_lane(args, dp: int):
    """The acceptance lane: from the naive default, find the fabric's
    config; must land within 10% of the hand-tuned recipe."""
    from deepspeed_tpu.models import gpt2_config
    from deepspeed_tpu.runtime.autotune import (SearchDriver,
                                                generate_candidates)

    model_cfg = gpt2_config(args.size, vocab_size=512,
                            max_seq_len=args.seq, dropout=0.0,
                            embed_dropout=0.0)
    gas = 2  # the BENCH round-13 shape: exchange N hides behind micro N+1
    cands, rejected = generate_candidates(
        dp=dp, stage=0, current_outer=1,
        wire_dtypes=("fp32", "bf16", "int8"),
        outers=(2,), overlap=(False, True))
    batches = _make_batches(dp, args.seq, 1)
    probe = _engine_probe_factory(model_cfg, dp, gas, args.steps,
                                  warmup=2, batches=batches)
    driver = SearchDriver(probe)
    best = driver.search(cands)
    assert best is not None and driver.complete, driver.trace()
    by_name = {r.candidate.name: r for r in driver.results if r.ok}
    naive = by_name["implicit"]
    hand_tuned = by_name["hier2_fp32_int8_overlap"]
    winner_ms = best.metrics["step_ms"]
    # the acceptance pin: the search (which starts blind) must discover
    # a config within 10% of the hand-tuned BENCH recipe's ms/step
    assert winner_ms <= 1.10 * hand_tuned.metrics["step_ms"], \
        (best.candidate.name, winner_ms, hand_tuned.metrics["step_ms"])
    return {
        "candidates": len(cands), "rejected": rejected,
        "winner": best.candidate.name,
        "winner_ms": winner_ms,
        "naive_ms": naive.metrics["step_ms"],
        "hand_tuned": "hier2_fp32_int8_overlap",
        "hand_tuned_ms": hand_tuned.metrics["step_ms"],
        "speedup_vs_naive": round(
            naive.metrics["step_ms"] / max(winner_ms, 1e-9), 2),
        "winner_vs_hand_tuned": round(
            winner_ms / max(hand_tuned.metrics["step_ms"], 1e-9), 3),
        "trace": driver.trace(),
    }


def _retune_lane(args, dp: int, ledger_dir: str):
    """Injected wire slowdown -> exactly one online retune -> swap to
    the serial wire -> bitwise loss parity with the serial oracle.

    The lane runs the outer=2 HIERARCHICAL fp32 wire: cross-process,
    overlap<->serial is bitwise only where the reduction orders
    coincide — gather-structured exchanges and outer==2 hierarchies
    (the PR-9 parity contract; gloo's flat in-program psum rotates
    chunk association, so a FLAT fp32 overlap/serial pair differs by
    reduction-order rounding on this fabric).  outer=2 is also the
    recommended deployment shape the search lane lands on."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config

    model_cfg = gpt2_config(args.size, vocab_size=512,
                            max_seq_len=args.seq, dropout=0.0,
                            embed_dropout=0.0)
    gas = 2
    n_steps = 18
    slow_from = 7
    batches = _make_batches(dp, args.seq, 1)
    ledger_path = os.path.join(ledger_dir, "autotune_retune.jsonl")

    def run(overlap: bool, online: bool):
        cfg = {
            "train_batch_size": dp * gas,
            "train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 0},
            "mesh": {"data": dp}, "steps_per_print": 0,
            "optimizer": {"type": "Adam",
                          "params": {"lr": 1e-4, "weight_decay": 0.0}},
            "comm": {"gradient_reduction": "bucketed",
                     "wire_dtype": "fp32", "hierarchy": {"outer": 2},
                     "overlap": "on" if overlap else "none"},
        }
        if online:
            cfg["autotune"] = {
                "enabled": True, "probe_steps": 1, "probe_warmup": 1,
                "ledger_path": ledger_path,
                "min_improvement": 0.05,
                "online": {"enabled": True, "window": 3,
                           "baseline_steps": 3, "threshold": 1.4,
                           "cooldown_steps": 4, "check_every": 1,
                           "safe_only": True}}
            # the injected wire slowdown: every exchange send from
            # step `slow_from` pays a delay — the degraded-fabric
            # scenario the online retuner exists for
            cfg["faults"] = {"rules": [{
                "site": "exchange.send", "kind": "delay_ms",
                "delay_ms": 120,
                "steps": list(range(slow_from, n_steps + 1))}]}
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT(model_cfg), dist_init_required=False,
            config_params=cfg)
        losses = []
        try:
            for _ in range(n_steps):
                for _m in range(gas):
                    loss = engine.forward(batches[0])
                    engine.backward()
                engine.step()
                losses.append(float(loss))
            retunes = (engine._autotuner.retunes
                       if engine._autotuner is not None else 0)
            demoted = engine._overlap_mode is None
            return losses, retunes, demoted
        finally:
            engine.close_overlap()
            del engine
            gc.collect()

    if os.path.exists(ledger_path):
        os.remove(ledger_path)
    oracle, _r0, _d0 = run(overlap=False, online=False)
    retuned, retunes, swapped_serial = run(overlap=True, online=True)
    assert retunes == 1, f"expected exactly one online retune, got {retunes}"
    assert swapped_serial, "the retune did not swap off the overlap wire"
    assert [np.float32(a) for a in oracle] == \
        [np.float32(b) for b in retuned], \
        "loss parity broke across the online retune swap"
    events = []
    if os.path.exists(ledger_path):
        with open(ledger_path) as f:
            events = [json.loads(ln) for ln in f if ln.strip()]
    return {
        "steps": n_steps, "slowdown_from_step": slow_from,
        "injected_delay_ms": 120,
        "retunes": retunes, "swapped_to_serial": swapped_serial,
        "loss_bitwise_vs_serial_oracle": True,
        "ledger_events": [e["event"] for e in events],
        "final_loss": round(retuned[-1], 4),
    }


def bench_tcp(args, nproc: int, proc_id: int):
    import tempfile

    import jax

    dp = jax.device_count()
    ledger_dir = tempfile.mkdtemp(prefix=f"autotune_r{proc_id}_")
    search = _search_lane(args, dp)
    retune = _retune_lane(args, dp, ledger_dir)
    if proc_id == 0:
        print(json.dumps({
            "metric": "autotune_2proc_tcp",
            "platform": "cpu",
            "world": {"processes": nproc, "devices": dp},
            "steps": args.steps,
            "value": search["winner_vs_hand_tuned"],
            "unit": "winner_ms_over_hand_tuned_ms",
            "search": search,
            "retune": retune,
        }), flush=True)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker(args):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=args.coord,
                               num_processes=args.nproc,
                               process_id=args.proc_id)
    import deepspeed_tpu  # noqa: F401  (gloo flag before the CPU client)

    bench_tcp(args, args.nproc, args.proc_id)


def _record(out: str):
    try:
        line = next(ln for ln in out.splitlines()
                    if ln.startswith("{") and "metric" in ln)
        result = json.loads(line)
        from deepspeed_tpu.monitor.artifacts import record_bench_result

        path = record_bench_result(result)
        print(f"recorded: {path}", file=sys.stderr)
    except Exception as e:
        print(f"artifact recording failed: {e}", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nproc", type=int, default=1)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--size", default="nano")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--no-record", dest="no_record", action="store_true",
                    help="skip the durable bench_artifacts/runs record "
                         "(CI/test invocations)")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--proc-id", dest="proc_id", type=int, default=0)
    ap.add_argument("--coord", default="")
    args = ap.parse_args()
    if args.worker:
        worker(args)
        return
    if args.nproc <= 1:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import jax

        jax.config.update("jax_platforms", "cpu")
        import tempfile

        result = run_dry(tempfile.mkdtemp(prefix="autotune_dry_"))
        print(json.dumps(result, indent=2, default=str))
        if not args.no_record:
            # re-record into the repo's durable artifact dir
            from deepspeed_tpu.monitor.artifacts import record_bench_result

            result.pop("artifact", None)
            path = record_bench_result(result)
            print(f"recorded: {path}", file=sys.stderr)
        return
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(args.nproc):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--proc-id", str(pid), "--coord", coord,
             "--nproc", str(args.nproc), "--steps", str(args.steps),
             "--size", args.size, "--seq", str(args.seq)],
            stdout=subprocess.PIPE if pid == 0 else subprocess.DEVNULL,
            stderr=subprocess.STDOUT if pid == 0 else subprocess.DEVNULL,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}))
    out, _ = procs[0].communicate(timeout=3600)
    for p in procs[1:]:
        p.wait(timeout=120)
    out = out.decode()
    sys.stdout.write(out)
    if any(p.returncode for p in procs):
        sys.exit(1)
    if not args.no_record:
        _record(out)


if __name__ == "__main__":
    main()
