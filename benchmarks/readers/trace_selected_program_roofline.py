"""`trace_routed_program_roofline` for a program whose queries attend a
learned selection of the cached rows: the family's cost function is
handed BOTH what the step scored to choose (index keys) and what it
chose (latent rows), each from the program's own counter, because
neither follows from the other — a query scores every cached key and
attends at most `index_topk` rows.  args: `program` (the jitted
function's name on `XLA Modules`), `cost` (`cost(config, keys_scored,
rows_selected, batch, experts_touched) -> (operations, bytes)`), `keys`
and `rows` (the counters whose bytes are the index keys scored and the
latent rows selected, summed over the layers that do either), `steps`
(the counter whose calls are the runs and whose bytes are their batch)
and `experts` (the counter whose bytes are experts touched and whose
calls are routed-layer calls).

The least time is the larger of operations over the peak rate and bytes
over the peak bandwidth (`peaks.json`); keys, rows, batch and experts
are means over the whole window, the device time the median of the
traced stretch.  Returns None without a trace, without runs of the
program in it, where the family has no such function or the program has
no such counters (a program older than the counters)."""


def read(*, cell, run, trace, program: str, cost: str, keys: str, rows: str,
         steps: str, experts: str):
    fn = getattr(cell.family, cost, None)
    runs, touched = run.counters.get(steps), run.counters.get(experts)
    scored, chosen = run.counters.get(keys), run.counters.get(rows)
    if trace is None or fn is None or not runs or not runs["calls"] \
            or not touched or not touched["calls"] or not scored \
            or not chosen:
        return None
    times = sorted(trace.module_durations(program))
    if not times:
        return None
    n = runs["calls"]
    flops, nbytes = fn(cell.config, scored["bytes"] / n, chosen["bytes"] / n,
                       runs["bytes"] / n,
                       touched["bytes"] / touched["calls"])
    least = max(flops / cell.peaks["bf16_flops_per_s"],
                nbytes / cell.peaks["hbm_bytes_per_s"])
    return 100.0 * least / times[len(times) // 2]
