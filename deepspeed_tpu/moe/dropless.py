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

Four ways to the same sum y_t = sum_i w_ti E_i(x_t), chosen by
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
  (`lax.ragged_dot`) a matrix over the experts held and over all
  T * top_k rows, the results put back in token order.  top_k products
  a token: off a TPU a prefill chunk (any call over the ridge) and a
  decode step too small to touch every expert.  It is the oracle of the
  fourth way.
* `experts_slabs` (on a TPU): the same sort, and of its order only the
  rows this chip holds — a compact SLAB of the first `slab_rows` rows
  (twice the rows expected of the share held, in whole `SLAB_ROWS`;
  every row where all experts are held), each through its expert's FFN
  by a kernel that walks the held experts over their ranges of the slab
  and reads every expert's matrices once (the registry's
  `grouped_experts` op, kernels/moe_kernels.py), the results summed
  into token order under their weights.  A chunk that holds more rows
  than a slab walks the next slab too (`slabs_walked`: a loop of one
  body, usually one trip): nothing is dropped at any number of rows
  held.  A prefill chunk: any call over the ridge.

An expert has one of two forms, and `experts` says which by what it
holds: `gate`, `up` [E, D, F] and `down` [E, F, D] — a SiLU-gated FFN,
down(silu(gate x) * up x) — or `up` and `down` alone — down(relu(up x)
** 2).  `expert_hidden` (kernels/expert_form.py) is the one place that
knows: the four ways, both kernels (kernels/moe_kernels.py) and a shared
expert of the same form (`dense_expert`) call it; E, D and F are read
off `up`, which every form has, and `expert_matrices` counts what an
expert streams.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.expert_form import expert_hidden, expert_matrices

# tokens a call multiplies each streamed weight with before the products
# cost more than the bytes: ~240 on a v5e (197 TFLOP/s over 819 GB/s);
# half of it leaves the masked path bound by bytes with room
RIDGE_TOKENS = 128
# a slab of `experts_slabs` is whole multiples of this many rows, and
# holds this many times the rows expected of the share held
SLAB_ROWS = 256
SLAB_ROOM = 2


def dense_expert(p, x):
    """One expert of either form on every row (a shared expert): x
    [T, D] -> [T, D] float32, products at the weights' dtype."""
    dot = lambda a, w: jnp.dot(a.astype(w.dtype), w,
                               preferred_element_type=jnp.float32)
    return dot(expert_hidden(lambda w: dot(x, w), p), p["down"])


def route(h, router, top_k: int, scoring: str = "softmax",
          renormalize: bool = False, select_bias=None, scale: float = 1.0,
          renorm_eps: float = 0.0):
    """h [T, D], router [D, E] -> (weights [T, top_k] float32, experts
    [T, top_k] int32): the scores over E in float32, the top_k largest —
    of the scores plus `select_bias` [E] where one is given, which
    chooses and does not weigh — as they are or over their sum (plus
    `renorm_eps`, where a model adds one), times `scale`."""
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
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + renorm_eps if renorm_eps else total)
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
    h = expert_hidden(lambda w: _dot32(x, w, "td,edf->etf"), experts)
    out = _dot32(h, experts["down"], "etf,efd->etd")
    return jnp.einsum("etd,te->td", out, w)


def experts_masked(x, experts, weights, idx):
    """Every expert on every token, weighted 0 where the token did not
    choose it; x [T, D] -> [T, D] float32."""
    return experts_weighted(
        x, experts, combine_weights(weights, idx, experts["up"].shape[0]))


def _by_expert(idx, num_experts: int, keep=None):
    """The assignments sorted by expert, as the grouped ways walk them:
    (order [T * top_k]: the flat assignments in the order of their
    experts, those where `keep` [T, top_k] is False behind every group;
    offsets [num_experts + 1]: expert e's rows of that order lie at
    `offsets[e]` .. `offsets[e + 1]`, the rows of no group from
    `offsets[-1]` on)."""
    flat = idx.reshape(-1)
    if keep is not None:
        flat = jnp.where(keep.reshape(-1), flat, num_experts)
    sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1, mode="drop")
    return jnp.argsort(flat, stable=True), jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])


def grouped_ffn(xs, experts, offsets):
    """Rows sorted by expert through their experts' FFN by grouped
    products (`lax.ragged_dot`): xs [C, D], expert e's rows at
    `offsets[e]` .. `offsets[e + 1]` (offsets [E + 1]) -> [C, D]
    float32, 0 from `offsets[E]` on: rows of no group hold nothing to
    rely on."""
    dt = experts["up"].dtype
    xs, sizes = xs.astype(dt), offsets[1:] - offsets[:-1]

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    h = expert_hidden(lambda w: grouped(xs, w), experts)
    out = grouped(h.astype(dt), experts["down"])
    return jnp.where(jnp.arange(xs.shape[0])[:, None] < offsets[-1],
                     out, 0.0)


def experts_grouped(x, experts, weights, idx, held=None):
    """Assignments sorted by expert, grouped products over the experts
    held; x [T, D] -> [T, D] float32.  With `held` [T, top_k] the
    assignments that lie elsewhere sort behind every group, belong to
    none and add nothing."""
    T, k = idx.shape
    order, offsets = _by_expert(idx, experts["up"].shape[0], held)
    out = grouped_ffn(x.astype(experts["up"].dtype)[order // k], experts,
                      offsets)                                 # [T*k, D]
    back = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    return jnp.einsum("tkd,tk->td", out[back].reshape(T, k, -1), weights)


def slab_rows(tokens: int, top_k: int, count: int, total: int) -> int:
    """Rows of a slab of `experts_slabs` for a call of `tokens` rows of
    `top_k` assignments of which a share `count / total` is held:
    `SLAB_ROOM` times the rows expected, in whole `SLAB_ROWS`, and never
    more than there are."""
    rows = tokens * top_k
    expected = -(-rows * count // total)
    return min(rows, -(-SLAB_ROOM * expected // SLAB_ROWS) * SLAB_ROWS)


def _kept(held, live, top_k: int):
    """The assignments a slab may hold, [T, top_k] bool (None: all):
    those `held`, of a token that is `live`."""
    if live is None:
        return held
    live = jnp.broadcast_to(live[:, None], (live.shape[0], top_k))
    return live if held is None else held & live


def slabs_walked(idx, experts, total=None, held=None, live=None):
    """(slabs `experts_slabs` walks for this call — int32, or the int 1
    where one slab holds every row —, rows of a slab)."""
    T, k = idx.shape
    E = experts["up"].shape[0]
    C = slab_rows(T, k, E, E if held is None or total is None else total)
    if C == T * k:
        return 1, C
    return -(-jnp.sum(_kept(held, live, k), dtype=jnp.int32) // C), C


def grouped_info(tokens: int, rows: int, experts) -> dict:
    """What the kernel registry may look at to choose the product over
    a slab of `rows` rows, of a call of `tokens` rows over `experts`."""
    return dict(touched_info(tokens, experts), rows=rows)


def experts_slabs(x, experts, weights, idx, total=None, held=None,
                  live=None):
    """Assignments sorted by expert and the held ones walked a slab at a
    time through the registry's `grouped_experts` op; x [T, D] -> [T, D]
    float32.  An assignment that lies elsewhere, and one of a token
    that is not `live`, sorts behind every group: it enters no slab's
    sum, and the token's sum is 0."""
    from ..kernels import registry

    T, k = idx.shape
    slabs, C = slabs_walked(idx, experts, total, held, live)
    order, offsets = _by_expert(idx, experts["up"].shape[0],
                                _kept(held, live, k))
    order = jnp.pad(order, (0, -(T * k) % C))      # whole slabs
    xd, w = x.astype(experts["up"].dtype), weights.reshape(T * k)
    info = grouped_info(T, C, experts)

    def slab(s, y):
        rows = jax.lax.dynamic_slice(order, (s * C,), (C,))
        out = registry.dispatch(
            "grouped_experts", xd[rows // k], experts,
            jnp.clip(offsets - s * C, 0, C), info=info)
        # back to token order: a 0/1 [T, C] product with the weighted
        # rows, float32 throughout
        mine = (rows // k)[None, :] == jnp.arange(T)[:, None]
        return y + jnp.dot(mine.astype(jnp.float32), out * w[rows][:, None],
                           precision=jax.lax.Precision.HIGHEST)

    y = jnp.zeros((T, x.shape[1]), jnp.float32)
    if isinstance(slabs, int):
        return slab(0, y)
    return jax.lax.while_loop(
        lambda c: c[0] < slabs, lambda c: (c[0] + 1, slab(*c)),
        (jnp.int32(0), y))[1]


def touched_info(tokens: int, experts) -> dict:
    """What the kernel registry may look at to choose the routed product
    of a call of `tokens` rows over `experts` (arrays or their shapes)."""
    E, D, F = experts["up"].shape
    return {"tokens": tokens, "num_experts": E, "model_dim": D,
            "expert_dim": F, "matrices": expert_matrices(experts),
            "itemsize": jnp.dtype(experts["up"].dtype).itemsize}


def experts_touched_only(x, experts, weights, idx, live=None, held=None):
    """Every touched expert on every token, through the registry's
    `touched_experts` op; x [T, D] -> [T, D] float32.  An assignment of
    a token that is not `live` [T] weighs 0 and touches nothing."""
    from ..kernels import registry

    E = experts["up"].shape[0]
    if live is None:
        live = jnp.ones((x.shape[0],), bool)
    ids, n = touched_list(idx, live, E, held)
    w = combine_weights(jnp.where(live[:, None], weights, 0.0), idx, E)
    return registry.dispatch("touched_experts", x, experts, w, ids, n,
                             info=touched_info(x.shape[0], experts))


def routed_way(tokens: int, top_k: int, experts, total=None) -> str:
    """Which of the four ways `routed_experts` takes for a call of
    `tokens` rows of `top_k` assignments over `experts` (arrays or their
    shapes), a share of `total`: "touched", "masked", "slabs" or
    "grouped"."""
    from ..kernels import registry

    E = experts["up"].shape[0]
    total = E if total is None else total
    # on a TPU, under the ridge: never more bytes than the masked way
    if registry.resolve_impl("touched_experts",
                             info=touched_info(tokens, experts)) == "pallas":
        return "touched"
    # the assignments expected here, T * k * E / total, cover the E held
    if tokens * top_k >= total and tokens <= RIDGE_TOKENS:
        return "masked"
    # on a TPU, over the ridge: the held rows alone, each expert read once
    if registry.resolve_impl(
            "grouped_experts", info=grouped_info(
                tokens, slab_rows(tokens, top_k, E, total),
                experts)) == "pallas":
        return "slabs"
    return "grouped"


def routed_words(tokens: int, top_k: int, experts, total=None) -> str:
    """`routed_way` in words, for a log line at build time: the way a
    call of `tokens` rows takes here, and, where that is not the kernel's
    walk, the registry's own reason for refusing it."""
    from ..kernels import registry

    way = routed_way(tokens, top_k, experts, total)
    if way in ("touched", "slabs"):
        return f"{tokens} rows take the {way!r} way (the kernel's walk)"
    E = experts["up"].shape[0]
    op, info = ("touched_experts", touched_info(tokens, experts)) \
        if tokens <= RIDGE_TOKENS else ("grouped_experts", grouped_info(
            tokens, slab_rows(tokens, top_k, E, total or E), experts))
    kernel = registry.get_kernel(op)
    why = kernel.auto_supports("default", info)[1] or \
        kernel.compatibility_message()
    return f"{tokens} rows take the {way!r} way ({op}: {why})"


def rows_multiplied(idx, experts, total=None, held=None, live=None):
    """Assignment rows the routed product of this call multiplies with
    an expert's matrices, int32: the slabs walked times a slab's rows,
    every assignment where they are grouped whole, every row times the
    experts held (or touched) where each expert runs on every row."""
    T, k = idx.shape
    E = experts["up"].shape[0]
    way = routed_way(T, k, experts, total)
    if way == "slabs":
        slabs, C = slabs_walked(idx, experts, total, held, live)
        return jnp.int32(slabs * C)
    if way == "grouped":
        return jnp.int32(T * k)
    if way == "masked":
        return jnp.int32(T * E)
    return T * experts_touched(
        idx, jnp.ones((T,), bool) if live is None else live, E, held)


def routed_experts(x, experts, weights, idx, total=None, held=None,
                   live=None):
    """sum_i w_ti E_i(x_t) for x [T, D], by the cheapest of the four
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
    if way == "slabs":
        return experts_slabs(x, experts, weights, idx, total, held, live)
    return experts_grouped(x, experts, weights, idx, held)
