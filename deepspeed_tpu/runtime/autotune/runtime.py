"""The engine attachment: search/retune orchestration.

One AutotuneRuntime hangs off each engine (config block "autotune").
It owns:

* `search()` — the fingerprinted, cached config search: winner-cache
  lookup first (a hit applies with ZERO probes and counts
  `autotune.cache_hits`; a fingerprint mismatch re-probes LOUDLY), else
  a budgeted live probe sweep over the legal candidate space, the
  winner applied through the StepBuilder rebuild and stored back keyed
  by (model shape, mesh, fabric)
* the ONLINE retune loop — `on_step_boundary()` (called from the
  engine's step() tail) feeds wall ms/step + exposed-wire creep into a
  RegressionDetector; a sustained regression re-probes a bounded
  1-knob neighborhood of the incumbent at the next boundary and swaps
  the winning program in live.  Online swaps default to
  numerics-safe candidates only (`online.safe_only`), so the loss
  stream stays BITWISE across a swap — the parity the chaos lane pins.
* multi-process agreement — step timing jitters per rank, so on a
  multi-process mesh the trigger verdict and the swap decision both
  ride a hostwire allgather (every `online.check_every` boundaries);
  every rank then probes the same candidates in the same order and
  applies rank 0's decision.  Divergent per-rank swaps would deadlock
  the next collective; this is the same lockstep discipline as the
  PR-10 demotion barrier, at the cadence of a KV allgather.
* the `autotune.jsonl` ledger (rank 0, monitor run dir) the report
  renders, and the `autotune.*` counters.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from ...monitor.counters import COUNTERS
from ...utils.logging import log_dist, logger
from .cache import WinnerCache
from .driver import SearchDriver
from .fingerprint import engine_fingerprint
from .online import RegressionDetector
from .probe import EngineProber, apply_candidate
from .space import (Candidate, current_candidate, generate_candidates,
                    neighborhood)


class _Consensus:
    """Rank-agreement over the hostwire KV: single-process short-
    circuits, multi-process allgathers a small JSON payload.  Collective
    contract: every rank must call agree() at the same boundary."""

    def __init__(self, tag: str = "dstpu-autotune"):
        try:
            import jax

            self.world = jax.process_count()
        except Exception:
            self.world = 1
        self._wire = None
        self.tag = tag

    def agree(self, obj: Any) -> List[Any]:
        if self.world <= 1:
            return [obj]
        if self._wire is None:
            from ..comm.hostwire import HostWire

            self._wire = HostWire(tag=self.tag)
        payloads = self._wire.allgather_bytes(
            json.dumps(obj, default=str).encode())
        return [json.loads(p.decode()) for p in payloads]


class AutotuneRuntime:
    def __init__(self, engine, config):
        self.engine = engine
        self.config = config
        self.detector = RegressionDetector(
            window=config.online_window,
            baseline_steps=config.online_baseline_steps,
            threshold=config.online_threshold,
            exposed_threshold_ms=config.online_exposed_threshold_ms,
            cooldown_steps=config.online_cooldown_steps)
        self._consensus = _Consensus()
        self._last_boundary_t: Optional[float] = None
        self._exposed_snap = self._exposed_us()
        self._local_trigger: Optional[str] = None
        self.retunes = 0
        self._ledger_path = self._resolve_ledger_path()

    # -- plumbing --------------------------------------------------------

    def _resolve_ledger_path(self) -> Optional[str]:
        if self.config.ledger_path:
            return self.config.ledger_path
        rm = getattr(self.engine, "run_monitor", None)
        if rm is not None:
            return os.path.join(rm.run_dir, "autotune.jsonl")
        return None

    def _rank(self) -> int:
        try:
            import jax

            return jax.process_index()
        except Exception:
            return 0

    def ledger(self, event: str, **fields) -> None:
        """Append one ledger row (rank 0; the report renders these)."""
        if self._ledger_path is None or self._rank() != 0:
            return
        row = {"t": time.time(), "event": event,
               "step": self.engine.global_steps, **fields}
        try:
            with open(self._ledger_path, "a") as f:
                f.write(json.dumps(row, default=str) + "\n")
        except OSError as e:
            logger.warning(f"autotune ledger {self._ledger_path}: {e}")

    @staticmethod
    def _exposed_us() -> int:
        return COUNTERS.snapshot().get("grad_wire.exposed_ms", (0, 0))[1]

    # -- the candidate space ---------------------------------------------

    def candidates(self, live_only: bool = True,
                   safe_only: bool = False) -> List[Candidate]:
        eng = self.engine
        cands, rejected = generate_candidates(
            dp=eng.dp_world_size,
            stage=eng._config.zero_optimization_stage,
            current_outer=eng.mesh_info.data_outer_size,
            wire_dtypes=self.config.wire_dtypes,
            overlap=((False, True) if self.config.include_overlap
                     else (False,)),
            bucket_sizes=self.config.bucket_sizes)
        if rejected:
            COUNTERS.add("autotune.rejected", calls=rejected)
        if live_only:
            cands = [c for c in cands if c.scope == "live"]
        if safe_only:
            cands = [c for c in cands if c.safe_numerics]
        return cands

    # -- the fingerprinted search ----------------------------------------

    def search(self, batch=None, candidates: Optional[List[Candidate]] = None,
               force: bool = False,
               cache_path: Optional[str] = None) -> Dict[str, Any]:
        """Search the live candidate space and (by default) apply the
        winner.  Cache hit => ZERO probes.  Returns the outcome dict
        ({"winner", "cached", "probes", "trace", ...})."""
        eng = self.engine
        if batch is not None:
            eng._autotune_batch = eng._shard_batch(batch)
        fp = engine_fingerprint(eng)
        cache = WinnerCache(cache_path or self.config.cache_path)
        if not force:
            hit = cache.lookup(fp)
            if self._consensus.world > 1:
                # lockstep the cache decision: rank 0's lookup rules —
                # a torn/missing cache file on ONE rank must not send
                # it probing (collective step programs) while the
                # others early-return on their hit
                agreed = self._consensus.agree(
                    None if hit is None else hit["winner"])[0]
                hit = None if agreed is None else {"winner": agreed}
            if hit is not None:
                winner = hit["winner"]
                cand = Candidate(
                    name=winner["name"], comm=winner["comm"],
                    stage=winner.get("stage", 0), scope="live",
                    safe_numerics=bool(winner.get("safe_numerics", False)))
                COUNTERS.add("autotune.cache_hits", calls=1)
                self.ledger("cache_hit", candidate=cand.name,
                            fingerprint=fp["digest"])
                log_dist(
                    f"autotune: cache hit for fingerprint {fp['digest']} "
                    f"-> {cand.describe()} (zero probes)", ranks=[0])
                if self.config.apply_winner:
                    self._apply(cand, reason="cached winner")
                return {"winner": cand.name, "candidate": cand,
                        "cached": True, "probes": 0, "trace": [],
                        "fingerprint": fp}
        cands = candidates if candidates is not None else self.candidates()
        incumbent = current_candidate(eng)
        prober = EngineProber(eng, steps=self.config.probe_steps,
                              warmup=self.config.probe_warmup)
        driver = self._make_driver(prober)
        baseline = prober.probe_current()
        best = self._search(driver, cands)
        trace = driver.trace()
        self.ledger("search", fingerprint=fp["digest"],
                    probes=len(driver.results),
                    baseline_ms=baseline["step_ms"],
                    trace=trace)
        # one decision for every rank: rank 0's measurements rule
        decision = self._decide(incumbent, baseline, best)
        winner_cand = incumbent
        if decision["swap"]:
            winner_cand = next(c for c in cands
                               if c.name == decision["winner"])
            if self.config.apply_winner:
                self._apply(winner_cand,
                            reason=f"search winner ({decision['why']})")
        # never pin a future run to a degraded probe set; rank 0 writes
        # (every rank racing read-modify-write of one shared cache file
        # with rank-local traces would be last-writer-wins gibberish)
        if driver.complete and self._rank() == 0:
            cache.store(fp, {
                "name": winner_cand.name, "comm": winner_cand.comm,
                "stage": winner_cand.stage,
                "safe_numerics": winner_cand.safe_numerics,
                # the ms attributed to the STORED winner: the rejected
                # challenger's number must not masquerade as the
                # incumbent's
                "step_ms": (decision.get("winner_ms") if decision["swap"]
                            else baseline["step_ms"])}, trace)
        return {"winner": winner_cand.name, "candidate": winner_cand,
                "cached": False, "probes": len(driver.results),
                "baseline_ms": baseline["step_ms"],
                "winner_ms": decision.get("winner_ms"),
                "trace": trace, "complete": driver.complete,
                "fingerprint": fp}

    def _make_driver(self, prober: EngineProber) -> SearchDriver:
        """Single-process: the driver enforces its own wall budget.
        Multi-process: the budget check must be LOCKSTEPPED (a rank
        whose local clock trips mid-sweep would skip a probe whose
        collective step program the others still dispatch), so the
        driver runs unbudgeted and _search gates each probe on rank
        0's clock through the consensus wire."""
        budget = self.config.budget_s if self._consensus.world <= 1 \
            else None

        def probe(cand):
            # trace timeline: each candidate probe is an `autotune`
            # span, so probe time reads as probing instead of an
            # anonymous slow step.  NOT gated on the engine's per-step
            # sampling — probes are rare and always worth a span.
            tr = getattr(self.engine, "_tracer", None)
            if tr is None:
                return prober.probe(cand)
            with tr.span("autotune.probe", "autotune", cand=cand.name,
                         step=self.engine.global_steps):
                return prober.probe(cand)

        return SearchDriver(probe, budget_s=budget)

    def _search(self, driver: SearchDriver, cands) -> Optional[Any]:
        if self._consensus.world <= 1:
            return driver.search(cands)
        from .driver import ProbeResult

        t0 = time.perf_counter()
        budget = self.config.budget_s
        best = None
        for cand in cands:
            exhausted = bool(budget is not None
                             and time.perf_counter() - t0 > budget)
            if self._consensus.agree(exhausted)[0]:  # rank 0 rules
                driver.results.append(ProbeResult(cand, skipped="budget"))
                continue
            r = driver.probe(cand)
            if r.ok and (best is None or r.score > best.score):
                best = r
        return best

    def _decide(self, incumbent: Candidate, baseline: Dict[str, Any],
                best) -> Dict[str, Any]:
        """Swap decision, agreed across ranks (rank 0's numbers)."""
        local = {
            "winner": best.candidate.name if best is not None else None,
            "winner_ms": (best.metrics.get("step_ms")
                          if best is not None else None),
            "baseline_ms": baseline.get("step_ms"),
        }
        agreed = self._consensus.agree(local)[0]
        swap = False
        why = "no candidate beat the incumbent"
        if agreed["winner"] is not None and agreed["winner_ms"] is not None:
            need = (1.0 - self.config.min_improvement) * \
                float(agreed["baseline_ms"] or 0.0)
            if agreed["winner"] != incumbent.name and \
                    float(agreed["winner_ms"]) < need:
                swap = True
                why = (f"{agreed['winner_ms']:.1f} ms/step vs incumbent "
                       f"{agreed['baseline_ms']:.1f} ms/step")
        return {"swap": swap, "winner": agreed["winner"],
                "winner_ms": agreed["winner_ms"], "why": why}

    def _apply(self, candidate: Candidate, reason: str) -> None:
        apply_candidate(self.engine, candidate)
        COUNTERS.add("autotune.swaps", calls=1)
        self.ledger("swap", candidate=candidate.name, reason=reason,
                    knobs=candidate.knobs())
        logger.warning(
            f"autotune SWAP at step {self.engine.global_steps}: "
            f"{candidate.describe()} ({reason})")

    # -- the online retune loop ------------------------------------------

    def on_step_boundary(self) -> None:
        """Called from the engine's step() tail (a clean post-apply
        state — the only point programs may be rebuilt, like the PR-10
        demotion).  Cheap when online retuning is off."""
        if not self.config.online_enabled:
            return
        now = time.perf_counter()
        exposed = self._exposed_us()
        if self._last_boundary_t is not None:
            step_ms = (now - self._last_boundary_t) * 1e3
            exposed_ms = (exposed - self._exposed_snap) / 1e3
            if self.detector.observe(step_ms, exposed_ms) and \
                    self._local_trigger is None:
                self._local_trigger = self.detector.describe_trigger(
                    step_ms, exposed_ms)
        self._exposed_snap = exposed
        step = self.engine.global_steps
        if step > 0 and step % self.config.online_check_every == 0:
            verdicts = self._consensus.agree(self._local_trigger)
            reasons = [v for v in verdicts if v]
            if reasons:
                try:
                    self.retune(reason=reasons[0])
                except Exception as e:
                    # the BACKGROUND loop must never kill training: a
                    # failed retune logs, re-baselines, and the run
                    # continues on the incumbent config
                    logger.warning(
                        f"autotune online retune failed "
                        f"({type(e).__name__}: {e}); the incumbent "
                        "config stands and training continues")
                    self.detector.reset(cooldown=True)
                    self._last_boundary_t = None
            self._local_trigger = None
        # stamp AFTER any retune: probe time must not read as a slow step
        self._last_boundary_t = time.perf_counter()

    def retune(self, reason: str) -> Dict[str, Any]:
        """One bounded online retune: re-probe the incumbent + its
        1-knob neighborhood, swap if a candidate clearly wins, then
        re-baseline the detector under whatever config emerged."""
        eng = self.engine
        COUNTERS.add("autotune.retunes", calls=1)
        self.retunes += 1
        tr = getattr(eng, "_tracer", None)
        if tr is not None:
            tr.instant("autotune.retune", "autotune", reason=reason,
                       step=eng.global_steps)
        incumbent = current_candidate(eng)
        cands = self.candidates(live_only=True,
                                safe_only=self.config.online_safe_only)
        neigh = neighborhood(incumbent, cands,
                             radius=self.config.online_radius)
        logger.warning(
            f"autotune ONLINE RETUNE at step {eng.global_steps}: {reason} "
            f"— re-probing {len(neigh)} neighbor(s) of "
            f"{incumbent.name}")
        prober = EngineProber(eng, steps=self.config.probe_steps,
                              warmup=self.config.probe_warmup)
        driver = self._make_driver(prober)
        baseline = prober.probe_current()
        best = self._search(driver, neigh)
        decision = self._decide(incumbent, baseline, best)
        self.ledger("retune", reason=reason, incumbent=incumbent.name,
                    baseline_ms=baseline["step_ms"],
                    probes=len(driver.results), trace=driver.trace(),
                    swapped=decision["swap"], winner=decision["winner"])
        if decision["swap"]:
            winner = next(c for c in neigh
                          if c.name == decision["winner"])
            self._apply(winner, reason=f"online retune: {reason}")
        else:
            log_dist(
                f"autotune online retune: incumbent {incumbent.name} "
                f"stands ({decision['why']})", ranks=[0])
        # re-baseline under the (possibly new) config; cooldown so one
        # fault burst cannot chain retunes
        self.detector.reset(cooldown=True)
        self._last_boundary_t = None
        return decision
