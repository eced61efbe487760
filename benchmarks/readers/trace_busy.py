"""The device's idle share of the traced window, in %: 100 * (1 - union of
`XLA Ops` intervals / window), averaged over the devices."""


def read(*, cell, run, trace):
    return None if trace is None else trace.idle_pct()
