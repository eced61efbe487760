"""The generic search driver: a budgeted, failure-tolerant probe loop.

Deliberately knows nothing about engines — `probe_fn(candidate)` is any
callable returning a metrics dict, so the same driver serves
tools/autotune_bench.py's synthetic cost surface and the engine
runtime's live StepBuilder probes.

Probe discipline:

* a probe is OPTIONAL: any failure (OOM, lowering error, transport
  fault) records the candidate as failed and moves on — the search
  must never die on a probe when the incumbent config would have run
* the wall budget is checked BEFORE each probe; exhausted means the
  remaining candidates record as skipped, and a search with skipped or
  failed probes reports `complete=False` so callers never pin a future
  run to a degraded probe set
* every probe's wall time lands in `autotune.probes` (bytes = µs, the
  ckpt.stall_ms convention)

The default scorer combines achieved throughput with the monitor-side
exposure counters: two candidates within measurement noise on ms/step
rank by how much of their time is EXPOSED wire/host wait (the creep the
online retuner watches), so the search prefers configs whose cost is
hidden behind compute."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

from ...monitor.counters import COUNTERS
from ...utils.logging import logger

# exposure metrics folded into the default score when a probe reports
# them (all in milliseconds per step, like `step_ms`)
EXPOSURE_KEYS = ("exposed_ms", "host_wait_ms", "a2a_exposed_ms")


def combine_score(metrics: Dict[str, Any],
                  exposure_weight: float = 0.5) -> float:
    """Higher is better.  Throughput first: `tokens_s` when the probe
    reports it, else 1000/step_ms (steps/s).  The exposure counters
    then discount the score by the fraction of step time the host spent
    visibly blocked — a config that is fast BECAUSE its wire hides
    beats one equally fast with the wire on the critical path, and the
    gap widens exactly when a degrading fabric would widen it."""
    if metrics.get("tokens_s"):
        base = float(metrics["tokens_s"])
    elif metrics.get("step_ms"):
        base = 1000.0 / float(metrics["step_ms"])
    else:
        raise ValueError(
            "probe metrics need 'tokens_s' or 'step_ms' to score; got "
            f"keys {sorted(metrics)}")
    step_ms = float(metrics.get("step_ms") or 0.0)
    if step_ms <= 0.0:
        return base
    exposed = sum(float(metrics.get(k) or 0.0) for k in EXPOSURE_KEYS)
    frac = min(1.0, exposed / step_ms)
    return base * (1.0 - exposure_weight * frac)


class ProbeResult:
    """One probed (or skipped/failed) candidate."""

    __slots__ = ("candidate", "metrics", "score", "error", "oom",
                 "skipped", "elapsed_s")

    def __init__(self, candidate, metrics=None, score=None, error=None,
                 oom=False, skipped=None, elapsed_s=0.0):
        self.candidate = candidate
        self.metrics = metrics
        self.score = score
        self.error = error
        self.oom = oom
        self.skipped = skipped
        self.elapsed_s = elapsed_s

    @property
    def ok(self) -> bool:
        return self.metrics is not None and self.error is None \
            and self.skipped is None

    def _candidate_name(self) -> str:
        name = getattr(self.candidate, "name", None)
        return name if name is not None else str(self.candidate)

    def trace(self) -> Dict[str, Any]:
        """Ledger/artifact row for this probe."""
        row: Dict[str, Any] = {"candidate": self._candidate_name()}
        if self.skipped is not None:
            row["skipped"] = self.skipped
        elif self.error is not None:
            row["failed"] = self.error
            if self.oom:
                row["oom"] = True
        else:
            row.update({k: (round(v, 3) if isinstance(v, float) else v)
                        for k, v in (self.metrics or {}).items()})
            if self.score is not None:
                row["score"] = round(float(self.score), 4)
        return row


def _is_oom(exc: BaseException) -> bool:
    s = str(exc)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s


class SearchDriver:
    """Budgeted probe loop over candidates; keeps every result for the
    trace the cache/ledger/artifact records."""

    def __init__(self, probe_fn: Callable[[Any], Dict[str, Any]],
                 score_fn: Callable[[Dict[str, Any]], float] = combine_score,
                 budget_s: Optional[float] = None):
        self.probe_fn = probe_fn
        self.score_fn = score_fn
        self.budget_s = budget_s
        self._t0 = time.perf_counter()
        self.results: List[ProbeResult] = []

    # -- budget ----------------------------------------------------------

    def elapsed_s(self) -> float:
        return time.perf_counter() - self._t0

    def budget_exhausted(self) -> bool:
        return self.budget_s is not None and self.elapsed_s() > self.budget_s

    # -- probing ---------------------------------------------------------

    def probe(self, candidate) -> ProbeResult:
        """Probe one candidate (budget- and failure-guarded); records
        and returns the result."""
        if self.budget_exhausted():
            r = ProbeResult(candidate, skipped="budget")
            self.results.append(r)
            return r
        t0 = time.perf_counter()
        try:
            metrics = self.probe_fn(candidate)
            r = ProbeResult(candidate, metrics=metrics,
                            score=self.score_fn(metrics),
                            elapsed_s=time.perf_counter() - t0)
        except Exception as exc:
            r = ProbeResult(candidate, error=type(exc).__name__,
                            oom=_is_oom(exc),
                            elapsed_s=time.perf_counter() - t0)
            logger.warning(
                f"autotune probe {r._candidate_name()} failed "
                f"({type(exc).__name__}: {exc}) — candidate skipped, "
                "search continues")
        COUNTERS.add("autotune.probes", int(r.elapsed_s * 1e6), calls=1)
        self.results.append(r)
        return r

    def search(self, candidates) -> Optional[ProbeResult]:
        """Probe every candidate; return the best-scoring successful
        result (None when nothing probed cleanly)."""
        best: Optional[ProbeResult] = None
        for cand in candidates:
            r = self.probe(cand)
            if r.ok and (best is None or r.score > best.score):
                best = r
        return best

    # -- outcome ---------------------------------------------------------

    @property
    def complete(self) -> bool:
        """True when no probe failed or was budget-skipped — the only
        state a winner may be CACHED from (never pin future rounds to
        a degraded probe)."""
        return all(r.ok for r in self.results)

    def trace(self) -> List[Dict[str, Any]]:
        return [r.trace() for r in self.results]
