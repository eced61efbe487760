"""The `serve` runner with a floor under how often the engine's token is
the reference's own: workload key `check.top1_agreement_floor`, beside
what `runners/serve.py` reads.

`serve`'s one limit, `logit_margin`, bounds how far the WORST chosen
token lies under the reference's largest logit.  Behind a top-k router
that worst position is a routing flip — rounding moves a token's last
chosen expert and the next one across each other, and a whole expert
appears or goes — at bf16 and at fp8 alike, so the margin that sound
runs need lets a lower precision pass; the precision shows in how OFTEN
the tokens differ, which `serve` reports (`top1_agreement`) and does not
limit.  This runner limits it.  Everything measured is `serve.run`'s,
untouched: one comparison is added to the numbers its check reports,
and it can only turn `correct` false.  A `benchmark` PR that gives
`runners/serve.py` the floor makes this file unnecessary (PERF.md
section 7, PR 37 and PR 44).
"""

from __future__ import annotations

from benchmarks.harness import RunResult
from benchmarks.runners import serve


def run(cell) -> RunResult:
    result = serve.run(cell)
    check = result.notes[-1]
    floor = cell.workload["check"]["top1_agreement_floor"]
    check["top1_agreement_floor"] = floor
    result.correct = result.correct and check["top1_agreement"] >= floor
    return result
