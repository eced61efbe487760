"""A dropless routed FFN for programs that serve: every token-expert
assignment the router makes is computed, at any batch — no capacity, no
overflow bucket, nothing dropped.  (The trainer's MoE, moe/layer.py, is
GShard capacity routing; this is what `serving/layers.py` and a served
model's uncached forward use.)

Routing: the router's product in float32 at full precision and its
scores over all E experts — their softmax, or the sigmoid of each
(`scoring`) — the `top_k` largest taken greedily, their weights used as
they are or, with `renormalize`, divided by their sum.

A share of the experts (`held` = (first, count), what expert parallelism
gives one chip): the router keeps its E outputs and its `top_k` a token,
and the weights are normalised over all the chosen; `held_assignments`
renumbers the chosen experts to the ones this chip holds and gives the
others weight 0, so an assignment to an expert that lies elsewhere adds
nothing here — what the other chips would add is theirs to add.

Two ways to the same sum y_t = sum_i w_ti E_i(x_t), chosen by
`routed_experts` from what the call can see (its static shapes):

* `experts_masked`: every expert on every token, weighted 0 where the
  token did not choose it.  Streams each expert's weights once and does
  E / top_k times the products, which is free while the call is bound by
  the weights' bytes: where its assignments cover the experts anyway
  (T * top_k >= E, counted over all E: of a share `count / E` of the
  assignments are held, `T * top_k * count / E >= count`) and T is
  under the chip's ridge (`RIDGE_TOKENS` operations a byte) — a decode
  step of tens of slots.
* `experts_grouped`: assignments sorted by expert, one grouped product
  (`lax.ragged_dot`) a matrix over the experts held, the results put
  back in token order.  top_k products a token: a prefill chunk, or a
  decode step too small to touch every expert.

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
          renormalize: bool = False):
    """h [T, D], router [D, E] -> (weights [T, top_k] float32, experts
    [T, top_k] int32): the scores over E in float32, the top_k largest,
    as they are or over their sum."""
    scores = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(scores, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(scores)
    weights, idx = jax.lax.top_k(scores, top_k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
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


def experts_touched(idx, live, num_experts: int, held=None):
    """Experts with at least one assignment from a live token: idx
    [T, top_k], live [T] bool -> int32 scalar; with `held` [T, top_k],
    among the assignments held."""
    hit, flat = jnp.zeros((num_experts,), jnp.int32), idx.reshape(-1)
    live = jnp.repeat(live.astype(jnp.int32), idx.shape[1])
    if held is not None:
        live = live * held.reshape(-1).astype(jnp.int32)
    return hit.at[flat].max(live).sum()


def _dot32(x, w, dims):
    return jnp.einsum(dims, x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def experts_masked(x, experts, weights, idx):
    """Every expert on every token; x [T, D] -> [T, D] float32."""
    E = experts["gate"].shape[0]
    # w[t, e]: the token's weight for expert e, 0 where it was not chosen
    w = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(weights)
    g = _dot32(x, experts["gate"], "td,edf->etf")
    u = _dot32(x, experts["up"], "td,edf->etf")
    out = _dot32(jax.nn.silu(g) * u, experts["down"], "etf,efd->etd")
    return jnp.einsum("etd,te->td", out, w)


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


def routed_experts(x, experts, weights, idx, total=None, held=None):
    """sum_i w_ti E_i(x_t) for x [T, D], by the cheaper of the two ways
    at this call's shapes.  `total` is the number of experts the router
    chose among where `experts` is a share of them, and `held`
    [T, top_k] the assignments of the share (`held_assignments`)."""
    T, k = idx.shape
    E = experts["gate"].shape[0]
    total = E if total is None else total
    # the assignments expected here, T * k * E / total, cover the E held
    if T * k >= total and T <= RIDGE_TOKENS:
        return experts_masked(x, experts, weights, idx)
    return experts_grouped(x, experts, weights, idx, held)
