"""runtime/autotune — the self-tuning runtime.

The repo grew ~15 interacting performance knobs (ZeRO stage, bucket
size, per-level wire dtypes, hierarchy factor, overlap mode, quant
block, gas/micro, remat, MoE dispatch/wire, prefetch depth) and the
winning combination is a property of the FABRIC, not the model (ZeRO++
arXiv:2306.10209; the Frontier low-bandwidth partitioning study
arXiv:2501.04266).  This package turns the knob space into a searched,
cached, live-retunable artifact:

  space.py        legal-candidate enumeration — every mutation is
                  validated through config.py's own parsers, so illegal
                  combos are pruned before a single probe runs
  fingerprint.py  (model shape, mesh, fabric) fingerprints keying the
                  winner cache — a cache probed on a different mesh
                  factorization, dtype config or world size must
                  re-probe loudly, never pin silently
  cache.py        the persisted winner cache, keyed by fingerprint
  driver.py       the generic search driver: budgeted probe loop,
                  failure-tolerant (a probe that OOMs is skipped, never
                  fatal), scorer combining achieved throughput with the
                  monitor's exposed-time counters
  probe.py        live probing on a RUNNING engine: candidate applied
                  via a StepBuilder program rebuild (the PR-10 demotion
                  path proved mid-run rebuilds safe), a few steps run
                  on state COPIES so training state never moves
  online.py       sustained-regression detection (step-time +
                  exposed-wire creep) driving the online retune loop
  runtime.py      the engine attachment: search/retune orchestration,
                  `autotune.*` counters, the ledger the report renders

Counters (monitor/counters.py): `autotune.probes` (bytes = probe µs,
the ckpt.stall_ms convention), `autotune.cache_hits`,
`autotune.rejected`, `autotune.swaps`, `autotune.retunes` — all
excluded from the comm byte table and rendered as the report's
"Autotune" section beside the `autotune.jsonl` ledger.
"""

from .cache import WinnerCache
from .driver import ProbeResult, SearchDriver, combine_score
from .fingerprint import (engine_fingerprint, fingerprint_diff,
                          make_fingerprint, serve_fingerprint)
from .online import RegressionDetector
from .probe import EngineProber
from .runtime import AutotuneRuntime
from .space import (Candidate, current_candidate,
                    current_serve_candidate, generate_candidates,
                    generate_serve_candidates, knob_distance,
                    neighborhood)

__all__ = [
    "AutotuneRuntime",
    "Candidate",
    "EngineProber",
    "ProbeResult",
    "RegressionDetector",
    "SearchDriver",
    "WinnerCache",
    "combine_score",
    "current_candidate",
    "current_serve_candidate",
    "engine_fingerprint",
    "fingerprint_diff",
    "generate_candidates",
    "generate_serve_candidates",
    "knob_distance",
    "make_fingerprint",
    "neighborhood",
    "serve_fingerprint",
]
