"""A kernel's share of its roofline, in %: the least time the chip could
take for the operations and bytes the kernel's calls need (the larger of
operations over the peak rate and bytes over the peak bandwidth, both from
peaks.json) over the device time of the kernel's events in the trace.

args: `op` (regular expression on the `XLA Ops` HLO text that picks the
kernel's events), `cost` (a function of the cell's family module,
`<cost>(config, batch, seq_len) -> (operations, bytes)` for one step),
`peak` (the peaks.json key the operations run against).  The runner says
how many steps were traced and at which shapes."""


def read(*, cell, run, trace, op: str, cost: str,
         peak: str = "bf16_flops_per_s"):
    if trace is None or not trace.matching_op_count(op):
        return None
    fn = getattr(cell.family, cost, None)
    steps = run.shapes.get("steps_traced")
    if fn is None or not steps:
        return None
    ops, nbytes = fn(cell.config, run.shapes["batch"], run.shapes["seq_len"])
    least = max(ops / cell.peaks[peak],
                nbytes / cell.peaks["hbm_bytes_per_s"])
    return 100.0 * least * steps / trace.matching_op_seconds(op)
