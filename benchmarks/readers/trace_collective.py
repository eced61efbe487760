"""Collective time from the trace, as % of the traced window.  args:
`part`: `exposed` (a collective runs and no other operation does on that
device) or `all`.  None where the trace has no collective."""


def read(*, cell, run, trace, part: str = "exposed"):
    if trace is None or not trace.collective_s():
        return None
    s = trace.collective_exposed_s() if part == "exposed" \
        else trace.collective_s()
    return 100.0 * s / trace.window_s
