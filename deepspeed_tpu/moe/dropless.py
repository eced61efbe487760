"""A dropless routed FFN for programs that serve: every token-expert
assignment the router makes is computed, at any batch — no capacity, no
overflow bucket, nothing dropped.  (The trainer's MoE, moe/layer.py, is
GShard capacity routing; this is what `serving/layers.py` and a served
model's uncached forward use.)

Routing: the router's product in float32 at full precision and its
scores over all E experts — their softmax, or the sigmoid of each
(`scoring`) — the `top_k` largest taken greedily, their weights used as
they are or, with `renormalize`, divided by their sum.  A selection bias
(`select_bias` [E], the `noaux_tc` routing of GLM-5.2) is added to the
scores only to CHOOSE the `top_k`: the weights are the unbiased scores
of the chosen, and `scale` multiplies them last.

A share of the experts (`held` = (first, count), what expert parallelism
gives one chip): the router keeps its E outputs and its `top_k` a token,
and the weights are normalised over all the chosen; `held_assignments`
renumbers the chosen experts to the ones this chip holds and gives the
others weight 0, so an assignment to an expert that lies elsewhere adds
nothing here — what the other chips would add is theirs to add.

Three ways to the same sum y_t = sum_i w_ti E_i(x_t), chosen by
`routed_experts` from what the call can see (its static shapes and its
backend):

* `experts_masked`: every expert on every token, weighted 0 where the
  token did not choose it.  Streams each expert's weights once and does
  E / top_k times the products, which is free while the call is bound by
  the weights' bytes: where its assignments cover the experts anyway
  (T * top_k >= E, counted over all E: of a share `count / E` of the
  assignments are held, `T * top_k * count / E >= count`) and T is
  under the chip's ridge (`RIDGE_TOKENS` operations a byte) — a decode
  step of tens of slots.  It is also the oracle of the third way.
* `experts_touched_only` (on a TPU): every TOUCHED expert on every
  token — the masked sum over the experts that an assignment of a live
  token chose here, by a list the program computes (`touched_list`) and
  a kernel that walks it (kernels/moe_kernels.py): an expert no live
  token chose is never read from HBM.  Never more bytes than the masked
  way and the same products an expert read, so any call under the ridge
  takes it, whatever its assignments cover: a decode step of 32 slots
  of which 12 are live streams the ~40 of 64 experts those chose, and
  the slots that are not live (`live` [T] False: their hidden rows are
  whatever the slot last held) touch nothing.
* `experts_grouped`: assignments sorted by expert, one grouped product
  (`lax.ragged_dot`) a matrix over the experts held, the results put
  back in token order.  top_k products a token: a prefill chunk (any
  call over the ridge), or off a TPU a decode step too small to touch
  every expert.

An expert is a SiLU-gated FFN; `experts` holds `gate`, `up` [E, D, F]
and `down` [E, F, D].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# tokens a call multiplies each streamed weight with before the products
# cost more than the bytes: ~240 on a v5e (197 TFLOP/s over 819 GB/s);
# half of it leaves the masked path bound by bytes with room
RIDGE_TOKENS = 128


def route(h, router, top_k: int, scoring: str = "softmax",
          renormalize: bool = False, select_bias=None, scale: float = 1.0):
    """h [T, D], router [D, E] -> (weights [T, top_k] float32, experts
    [T, top_k] int32): the scores over E in float32, the top_k largest —
    of the scores plus `select_bias` [E] where one is given, which
    chooses and does not weigh — as they are or over their sum, times
    `scale`."""
    scores = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(scores, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(scores)
    if select_bias is None:
        weights, idx = jax.lax.top_k(scores, top_k)
    else:
        _, idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32),
                               top_k)
        weights = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, idx.astype(jnp.int32)


def held_assignments(weights, idx, first: int, count: int):
    """The assignments as the chip that holds experts `first` ..
    `first + count - 1` sees them: (weights, experts numbered from 0
    among those held, held [T, top_k] bool).  An assignment to an expert
    that lies elsewhere keeps weight 0 and a number inside the range."""
    local = idx - first
    held = (local >= 0) & (local < count)
    return (jnp.where(held, weights, 0.0), jnp.clip(local, 0, count - 1),
            held)


def touched_list(idx, live, num_experts: int, held=None):
    """The experts with at least one assignment from a live token: idx
    [T, top_k], live [T] bool -> (ids [num_experts] int32: the touched
    experts in ascending order, the tail padded by repeating the last
    touched one — zeros where none is —, their count int32); with
    `held` [T, top_k], among the assignments held."""
    hit, flat = jnp.zeros((num_experts,), jnp.int32), idx.reshape(-1)
    live = jnp.repeat(live.astype(jnp.int32), idx.shape[1])
    if held is not None:
        live = live * held.reshape(-1).astype(jnp.int32)
    hit = hit.at[flat].max(live)
    n, at = hit.sum(), jnp.arange(num_experts, dtype=jnp.int32)
    # touched expert e goes to place (touched experts below e)
    ids = jnp.zeros_like(at).at[
        jnp.where(hit > 0, jnp.cumsum(hit) - 1, num_experts)].set(
            at, mode="drop")
    return jnp.where(at < n, ids, ids[jnp.maximum(n - 1, 0)]), n


def experts_touched(idx, live, num_experts: int, held=None):
    """How many experts `touched_list` lists: int32 scalar."""
    return touched_list(idx, live, num_experts, held)[1]


def _dot32(x, w, dims):
    return jnp.einsum(dims, x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def combine_weights(weights, idx, num_experts: int):
    """w [T, num_experts] float32: the token's weight for expert e, 0
    where it was not chosen."""
    T = idx.shape[0]
    return jnp.zeros((T, num_experts), jnp.float32).at[
        jnp.arange(T)[:, None], idx].add(weights)


def experts_weighted(x, experts, w):
    """Every expert on every token under the weights w [T, E] of
    `combine_weights`; x [T, D] -> [T, D] float32."""
    g = _dot32(x, experts["gate"], "td,edf->etf")
    u = _dot32(x, experts["up"], "td,edf->etf")
    out = _dot32(jax.nn.silu(g) * u, experts["down"], "etf,efd->etd")
    return jnp.einsum("etd,te->td", out, w)


def experts_masked(x, experts, weights, idx):
    """Every expert on every token, weighted 0 where the token did not
    choose it; x [T, D] -> [T, D] float32."""
    return experts_weighted(
        x, experts, combine_weights(weights, idx, experts["gate"].shape[0]))


def experts_grouped(x, experts, weights, idx, held=None):
    """Assignments sorted by expert, grouped products over the experts
    held; x [T, D] -> [T, D] float32.  With `held` [T, top_k] the
    assignments that lie elsewhere sort behind every group, belong to
    none and add nothing."""
    T, k = idx.shape
    E = experts["gate"].shape[0]
    flat = idx.reshape(T * k)
    if held is not None:
        flat = jnp.where(held.reshape(T * k), flat, E)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    dt = experts["gate"].dtype
    xs = x.astype(dt)[order // k]                              # [T*k, D]

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(grouped(xs, experts["gate"])) * \
        grouped(xs, experts["up"])
    out = grouped(h.astype(dt), experts["down"])               # [T*k, D]
    if held is not None:     # rows of no group hold nothing to rely on
        out = jnp.where((flat[order] < E)[:, None], out, 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    return jnp.einsum("tkd,tk->td", out[back].reshape(T, k, -1), weights)


def touched_info(tokens: int, experts) -> dict:
    """What the kernel registry may look at to choose the routed product
    of a call of `tokens` rows over `experts`."""
    E, D, F = experts["gate"].shape
    return {"tokens": tokens, "num_experts": E, "model_dim": D,
            "expert_dim": F,
            "itemsize": jnp.dtype(experts["gate"].dtype).itemsize}


def experts_touched_only(x, experts, weights, idx, live=None, held=None):
    """Every touched expert on every token, through the registry's
    `touched_experts` op; x [T, D] -> [T, D] float32.  An assignment of
    a token that is not `live` [T] weighs 0 and touches nothing."""
    from ..kernels import registry

    E = experts["gate"].shape[0]
    if live is None:
        live = jnp.ones((x.shape[0],), bool)
    ids, n = touched_list(idx, live, E, held)
    w = combine_weights(jnp.where(live[:, None], weights, 0.0), idx, E)
    return registry.dispatch("touched_experts", x, experts, w, ids, n,
                             info=touched_info(x.shape[0], experts))


def routed_way(tokens: int, top_k: int, experts, total=None) -> str:
    """Which of the three ways `routed_experts` takes for a call of
    `tokens` rows of `top_k` assignments over `experts` (arrays or their
    shapes), a share of `total`: "touched", "masked" or "grouped"."""
    from ..kernels import registry

    total = experts["gate"].shape[0] if total is None else total
    # on a TPU, under the ridge: never more bytes than the masked way
    if registry.resolve_impl("touched_experts",
                             info=touched_info(tokens, experts)) == "pallas":
        return "touched"
    # the assignments expected here, T * k * E / total, cover the E held
    if tokens * top_k >= total and tokens <= RIDGE_TOKENS:
        return "masked"
    return "grouped"


def routed_experts(x, experts, weights, idx, total=None, held=None,
                   live=None):
    """sum_i w_ti E_i(x_t) for x [T, D], by the cheapest of the three
    ways at this call's shapes and backend (`routed_way`).  `total` is
    the number of experts the router chose among where `experts` is a
    share of them, `held` [T, top_k] the assignments of the share
    (`held_assignments`) and `live` [T] the tokens whose sum anyone
    reads (all of them where it is None)."""
    way = routed_way(*idx.shape, experts, total)
    if way == "touched":
        return experts_touched_only(x, experts, weights, idx, live, held)
    if way == "masked":
        return experts_masked(x, experts, weights, idx)
    return experts_grouped(x, experts, weights, idx, held)
