"""A ratio (or a plain count) from the program's `COUNTERS`, taken over the
window.  args: `name` (the counter), `num` (`bytes` or `calls`), `den`
(optional: `bytes` or `calls` of the same counter, or of `den_name`),
`scale`."""


def read(*, cell, run, trace, name: str, num: str = "calls", den=None,
         den_name=None, scale: float = 1.0):
    c = run.counters.get(name)
    if not c:
        return None
    if den is None:
        return c[num] * scale
    d = run.counters.get(den_name or name, {}).get(den)
    return c[num] / d * scale if d else None
