"""`trace_program_roofline` for a program behind routed experts: the
family's cost function is also handed how many experts a routed layer
touched on average, from the program's own counter, because the weights a
step has to stream depend on it.  args: `program`, `cost`
(`cost(config, rows_read, batch, experts_touched) -> (operations,
bytes)`), `rows`, `steps` as there, and `experts` (the counter whose
bytes are experts touched and whose calls are routed-layer calls).

The least time, the median device time and every reason to return None
are `trace_program_roofline`'s own; None too where the program has no
such counter or the family no such function."""

import dataclasses
import types

from benchmarks.readers import trace_program_roofline


def read(*, cell, run, trace, program: str, cost: str, rows: str,
         steps: str, experts: str):
    fn = getattr(cell.family, cost, None)
    touched = run.counters.get(experts)
    if fn is None or not touched or not touched["calls"]:
        return None
    a_layer = touched["bytes"] / touched["calls"]
    family = types.SimpleNamespace(**{
        cost: lambda config, rows_read, batch:
        fn(config, rows_read, batch, a_layer)})
    return trace_program_roofline.read(
        cell=dataclasses.replace(cell, family=family), run=run, trace=trace,
        program=program, cost=cost, rows=rows, steps=steps)
