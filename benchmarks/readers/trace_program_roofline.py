"""A jitted program's share of its roofline: the least time one run of it
needs, from the family's cost function at what the program's counters say
a run did on average, over the median device time of its runs in the
trace.  args: `program` (the jitted function's name on `XLA Modules`,
e.g. `jit_decode`), `cost` (a function of the family module,
`cost(config, rows_read, batch) -> (operations, bytes)`), `rows` (the
counter whose bytes are the cache rows the runs read), `steps` (the
counter whose calls are the runs and whose bytes are their batch).

The least time is the larger of operations over the peak rate and bytes
over the peak bandwidth (`peaks.json`).  Rows and batch are means over
the whole window, the device time the median of the traced stretch.
Returns None without a trace, without runs of the program in it, where
the family has no such function or the program has no such counters."""


def read(*, cell, run, trace, program: str, cost: str, rows: str,
         steps: str):
    fn = getattr(cell.family, cost, None)
    runs, read_rows = run.counters.get(steps), run.counters.get(rows)
    if trace is None or fn is None or not runs or not read_rows \
            or not runs["calls"]:
        return None
    times = sorted(trace.module_durations(program))
    if not times:
        return None
    flops, nbytes = fn(cell.config, read_rows["bytes"] / runs["calls"],
                       runs["bytes"] / runs["calls"])
    least = max(flops / cell.peaks["bf16_flops_per_s"],
                nbytes / cell.peaks["hbm_bytes_per_s"])
    return 100.0 * least / times[len(times) // 2]
