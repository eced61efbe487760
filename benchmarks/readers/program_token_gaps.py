"""The gaps between a request's tokens as the ENGINE stamps them, from the
program's own recorder (`monitor/tracing.py`, sampling at 1): a
`first_token` instant carries its request's `rid` and `stamp_us`, a
`decode_step` span the `rids` its tokens went to and their `stamp_us` —
the instant `serving/engine.py` writes into `Request.token_times`, on the
recorder's clock.  Per request the stamps in order, the gaps between
consecutive ones pooled over the whole run, as the runner pools the
observer's.  args: `stat`: `median` or `p95` (ms), or `chunk_share`: the
share, in %, of those gaps inside which a `prefill_chunk` span began (a
gap that holds a prefill chunk).  None where no event carries a stamp."""

import bisect

from benchmarks.harness import STATS


def gaps_us(spans):
    """[(from, to)] in us, per request in stamp order, all pooled."""
    stamps = {}
    for e in spans:
        args = e.get("args", {})
        if "stamp_us" not in args:
            continue
        if e.get("name") == "first_token":
            stamps.setdefault(args["rid"], []).append(args["stamp_us"])
        elif e.get("name") == "decode_step":
            for rid in args.get("rids", ()):
                stamps.setdefault(rid, []).append(args["stamp_us"])
    return [(a, b) for ts in map(sorted, stamps.values())
            for a, b in zip(ts, ts[1:])]


def read(*, cell, run, trace, stat: str = "median"):
    gaps = gaps_us(run.program_spans)
    if not gaps:
        return None
    if stat != "chunk_share":
        return STATS[stat]([b - a for a, b in gaps]) / 1000.0
    chunks = sorted(e["ts"] for e in run.program_spans
                    if e.get("name") == "prefill_chunk")
    held = sum(bisect.bisect_right(chunks, b) > bisect.bisect_right(chunks, a)
               for a, b in gaps)
    return 100.0 * held / len(gaps)
