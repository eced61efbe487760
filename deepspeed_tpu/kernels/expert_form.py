"""What an expert of a routed FFN is, read off the matrices it holds.

An expert has one of two forms, and `experts` says which by what it
holds: `gate`, `up` [E, D, F] and `down` [E, F, D] — a SiLU-gated FFN,
down(silu(gate x) * up x) — or `up` and `down` alone — down(relu(up x)
** 2).  This is the one place that knows: the four ways of
moe/dropless.py, a shared expert of the same form (its `dense_expert`)
and both kernels of kernels/moe_kernels.py call `expert_hidden`; E, D
and F are read off `up`, which every form has, and `expert_matrices`
counts what an expert streams.  Pure arithmetic on a dict of matrices,
below both of its callers: it imports neither.
"""

from __future__ import annotations

import jax


def expert_hidden(dot, experts):
    """What an expert's `down` multiplies: `dot(w)` is the call's rows
    times the matrix (or matrices) `w` of `experts`, float32 — silu(gate
    x) * up x where the expert has a `gate`, relu(up x) ** 2 where it
    has none."""
    if "gate" in experts:
        g, u = dot(experts["gate"]), dot(experts["up"])
        return jax.nn.silu(g) * u
    # relu(u) ** 2 as relu(u) * u: XLA:CPU (jax 0.9) cannot run the bf16
    # product behind `square(relu(.))` under jit ("Unsupported element
    # type for DotThunk")
    u = dot(experts["up"])
    return jax.nn.relu(u) * u


def expert_matrices(experts) -> int:
    """Matrices of [D, F] values an expert holds: 3 (gate, up, down) or
    2 (up, down)."""
    return 3 if "gate" in experts else 2
