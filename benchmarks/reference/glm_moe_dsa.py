"""Plain reference for GLM-5.2 (`model_type` `glm_moe_dsa`): the forward
pass in straightforward `jax.numpy`, float32, matmuls at `highest`
precision, no kernel, no cache, no tiles, no grouping of tokens by
expert, the selection a plain `lax.top_k`.  For x [S, D] in layer l
(pre-norm, RMSNorm with a gain g and eps 1e-5, residual float32):

  h = RMSNorm(x).
  Attention, every layer:
    c_q = RMSNorm(h W_qa)                                  (q_lora_rank)
    [q_nope nope | q_rope rope] a head = c_q W_qb          (H heads)
    [c rank | k_r rope] = h W_kva,  c <- RMSNorm(c)
    q_rope and k_r rotated in INTERLEAVED pairs: dims 2i and 2i + 1 turn
      by p theta^(-2i/rope), no scaling.  [c | k_r] is the row a cache
      would hold.
    [k_nope nope | v] a head = c W_kvb
    a_ts = (q_nope_t . k_nope_s + q_rope_t . k_r_s) (nope + rope)^-1/2
    softmax over s in S_t, o_t = sum p v, output concat(o) W_o.
  Indexer, layers whose `indexer` entry is "full":
    q^I = c_q W^I_q           (Hi heads of Di)
    k^I_s = LayerNorm(h_s W^I_k) with scale and bias (Di; one key a token)
    the first `rope` dims of q^I and k^I rotated as above
    w_t = h_t W^I_w Hi^-1/2 Di^-1/2                        (Hi weights)
    I_ts = sum_j w_tj ReLU(q^I_tj . k^I_s) for s <= t
    S_t = the positions of the `topk` largest I_t. among s <= t (all of
      them while t < topk); ties to the lower position (`lax.top_k`).
  A layer marked "shared" has no indexer and attends S_t of the nearest
    "full" layer before it.
  FFN: layers l < `dense_layers`: (silu(h Wg) * h Wu) Wd.  Others:
    s = sigmoid(h W_r) over ALL the router's experts; the `top_k` with
    the largest s + b chosen (b: the layer's selection bias, which does
    not weigh); w_i = s_i / sum of the chosen s, times `route_scale`;
    y = sum_i w_i E_i(h) + Shared(h), every one a SiLU-gated FFN.
  Final RMSNorm, logits = x W_head (untied).

The share a chip holds: `first_expert` and the number of expert matrices
in the tree say which routed experts are here; the router keeps all its
outputs and the weights are normalised over all `top_k` chosen, and what
the absent experts would add is left out.  `wte` and `lm_head` are the
rows of the vocabulary held.

Departures (listed under `assumed` in the configuration file; the system
makes the same choices): no Hadamard rotation and no fp8 of q^I and k^I
(an orthogonal rotation of both leaves every dot product as it is; fp8
is a deployment's choice, the configuration states bf16); the index
key's LayerNorm has a bias and eps 1e-6; the FIRST `rope` dims of an
index head rotate (which ones is a column permutation under seeded
weights); "shared" layers carry no indexer weights; the
multi-token-prediction layer is left out; `head_dim: 192` of the
published config sizes nothing here (it equals qk_nope_head_dim).

Weights are the system's own tree (`deepspeed_tpu.models.glm_moe_dsa.
GlmMoeDsa.init`'s layout), upcast a piece at a time inside the jitted
pieces: attention a head and a block of queries at a time, the index
scores a block of queries at a time, a gated FFN a block of its width
at a time, the experts one at a time, the head a block of the vocabulary
at a time — at the timed sizes the reference runs beside the engine's
weights and pools (24,576 positions: the [S, S] selection 0.6 GB, the
stream and its norm 0.6 GB each, the logits 1.9 GB)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_BLOCK = 4096      # most vocabulary rows of one head product
QUERY_BLOCK = 1024     # most queries of one head's scores at a time
INDEX_BLOCK = 128      # most queries of one block of index scores
WIDTH_BLOCK = 2048     # most columns of one gated FFN product
NEG_INF = -1e30


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` needs, from a configuration file."""
    layers = config["held"]["layers"]
    return {"heads": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "rank": config["kv_lora_rank"],
            "index_heads": config["index_n_heads"],
            "topk": config["index_topk"],
            "indexer": tuple(config["indexer_types"][i] for i in layers),
            "dense_layers": config["first_k_dense_replace"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": config["held"]["first_expert"],
            "route_scale": float(config["routed_scaling_factor"]),
            "eps": config["rms_norm_eps"],
            "index_eps": config["assumed"]["index_norm_eps"],
            "theta": float(config["rope_parameters"]["rope_theta"])}


def _f32(a):
    return a.astype(jnp.float32)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(g)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) + \
        _f32(p["bias"])


def _rope_pairs(x, pos, theta):
    """x [S, ..., dr] at positions pos [S]: dims 2i and 2i + 1 rotated
    by pos theta^(-2i/dr)."""
    dr = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * \
        theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    for _ in range(x.ndim - 2):
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _cols(m, at, n):
    return jax.lax.dynamic_slice_in_dim(m, at, n, axis=1)


def _rows(m, at, n):
    return jax.lax.dynamic_slice_in_dim(m, at, n, axis=0)


def _selection(h, c_q, p, *, index_heads, rope, topk, index_eps, theta):
    """The rows every query attends: h [S, D], c_q [S, q_lora_rank] ->
    [S, S] bool, a block of queries at a time."""
    S = h.shape[0]
    pos = jnp.arange(S)
    di = p["k"].shape[1]
    turn = lambda x, at: jnp.concatenate(
        [_rope_pairs(x[..., :rope], at, theta), x[..., rope:]], axis=-1)
    keys = turn(_layer_norm(h @ _f32(p["k"]), p["k_norm"], index_eps), pos)
    qb = _divisor(S, INDEX_BLOCK)
    k = min(topk, S)

    def block(i):
        at = i * qb + jnp.arange(qb)
        q = turn((_rows(c_q, i * qb, qb) @ _f32(p["q"])).reshape(
            qb, index_heads, di), at)
        w = _rows(h, i * qb, qb) @ _f32(p["w"]) * \
            (index_heads ** -0.5 * di ** -0.5)
        scores = jnp.einsum("qhk,qh->qk", jax.nn.relu(
            jnp.einsum("qhd,kd->qhk", q, keys)), w)
        seen = pos[None, :] <= at[:, None]
        top, rows = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
        return jnp.zeros((qb, S), bool).at[
            jnp.arange(qb)[:, None], rows].set(top > -jnp.inf)

    return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, S)


def _attention(c_q, c, k_r, chosen, p, *, heads, nope, rope, theta):
    """c_q [S, q_lora_rank], c [S, rank], k_r [S, rope] (rotated),
    chosen [S, S] bool -> attention's output [S, D]: a head at a time, a
    block of queries at a time."""
    S = c.shape[0]
    pos = jnp.arange(S)
    v = p["kv_b"].shape[1] // heads - nope
    qb = _divisor(S, QUERY_BLOCK)
    scale = (nope + rope) ** -0.5

    def head(acc, n):
        q = c_q @ _f32(_cols(p["q_b"], n * (nope + rope), nope + rope))
        q_nope, q_rope = q[:, :nope], _rope_pairs(q[:, nope:], pos, theta)
        kv = c @ _f32(_cols(p["kv_b"], n * (nope + v), nope + v))
        k_nope, val = kv[:, :nope], kv[:, nope:]

        def block(i):
            s = (_rows(q_nope, i * qb, qb) @ k_nope.T +
                 _rows(q_rope, i * qb, qb) @ k_r.T) * scale
            pr = jax.nn.softmax(
                jnp.where(_rows(chosen, i * qb, qb), s, NEG_INF), axis=-1)
            return pr @ val

        o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, v)
        return acc + o @ _f32(_rows(p["o"], n * v, v)), None

    out, _ = jax.lax.scan(head, jnp.zeros((S, p["o"].shape[1]), jnp.float32),
                          jnp.arange(heads))
    return out


def _gated(h, gate, up, down):
    """(silu(h Wg) * h Wu) Wd, a block of the width at a time."""
    F = gate.shape[1]
    fb = _divisor(F, WIDTH_BLOCK)

    def one(acc, i):
        a = jax.nn.silu(h @ _f32(_cols(gate, i * fb, fb))) * \
            (h @ _f32(_cols(up, i * fb, fb)))
        return acc + a @ _f32(_rows(down, i * fb, fb)), None

    out, _ = jax.lax.scan(one, jnp.zeros((h.shape[0], down.shape[1]),
                                         jnp.float32), jnp.arange(F // fb))
    return out


def _routed(h, p, *, top_k, first_expert, route_scale):
    """h [S, D] -> routed + shared: every held expert for every token,
    weighted by its renormalised, scaled sigmoid score where the biased
    score puts it among the token's `top_k` of ALL the router's experts,
    by 0 elsewhere; the shared expert once."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))                  # [S, E]
    biased = s + _f32(p["select_bias"])
    kth = jax.lax.top_k(biased, top_k)[0][..., -1:]
    # greedy top-k keeps the first of equal scores; scores of seeded
    # float32 weights do not tie exactly
    chosen = jnp.where(biased >= kth, s, 0.0)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * route_scale
    e = p["experts"]
    w_held = _cols(w, first_expert, e["gate"].shape[0])

    def one(acc, args):
        gate, up, down, we = args
        return acc + we[:, None] * _gated(h, gate, up, down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (e["gate"], e["up"], e["down"], w_held.T))
    sh = p["shared"]
    return routed + _gated(h, sh["gate"], sh["up"], sh["down"])


@functools.partial(jax.jit, static_argnames=(
    "full", "dense", "heads", "nope", "rope", "rank", "index_heads", "topk",
    "top_k", "first_expert", "route_scale", "eps", "index_eps", "theta"))
def _layer(x, chosen, p, *, full, dense, heads, nope, rope, rank,
           index_heads, topk, top_k, first_expert, route_scale, eps,
           index_eps, theta):
    """x [B, S, D] float32 and the selection [B, S, S] handed on (None
    before the first layer) -> (x after the layer, the selection this
    layer attended)."""
    with jax.default_matmul_precision(HIGHEST):
        def one(args):
            xs, sel = args
            a = p["attn"]
            h = _rms_norm(xs, p["ln1"]["scale"], eps)
            pos = jnp.arange(xs.shape[0])
            c_q = _rms_norm(h @ _f32(a["q_a"]), a["q_norm"]["scale"], eps)
            ckr = h @ _f32(a["kv_a"])
            c = _rms_norm(ckr[:, :rank], a["kv_norm"]["scale"], eps)
            k_r = _rope_pairs(ckr[:, rank:], pos, theta)
            if full:
                sel = _selection(h, c_q, a["indexer"],
                                 index_heads=index_heads, rope=rope,
                                 topk=topk, index_eps=index_eps, theta=theta)
            xs = xs + _attention(c_q, c, k_r, sel, a, heads=heads, nope=nope,
                                 rope=rope, theta=theta)
            h = _rms_norm(xs, p["ln2"]["scale"], eps)
            if dense:
                m = p["mlp"]
                return xs + _gated(h, m["gate"], m["up"], m["down"]), sel
            return xs + _routed(h, p["mlp"], top_k=top_k,
                                first_expert=first_expert,
                                route_scale=route_scale), sel

        if chosen is None:
            chosen = jnp.zeros(x.shape[:2] + x.shape[1:2], bool)
        return jax.lax.map(one, (x, chosen))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, w, *, eps):
    """Final norm and the untied head, a block of the vocabulary at a
    time, written into the one [B, S, V] array."""
    with jax.default_matmul_precision(HIGHEST):
        h = _rms_norm(x, g, eps)
        V = w.shape[1]
        blk = _divisor(V, HEAD_BLOCK)

        def one(i, out):
            return jax.lax.dynamic_update_slice_in_dim(
                out, h @ _f32(_cols(w, i * blk, blk)), i * blk, axis=2)

        return jax.lax.fori_loop(
            0, V // blk, one, jnp.zeros(x.shape[:2] + (V,), jnp.float32))


def logits(params, tokens, *, heads, nope, rope, rank, index_heads, topk,
           indexer, dense_layers, top_k, first_expert, route_scale, eps,
           index_eps, theta, return_selected: bool = False):
    """tokens [B, S] int32 -> [B, S, V] float32 over the rows of the
    vocabulary held; with `return_selected` also the selections
    [B, S, S] bool of the "full" layers, in layer order."""
    x = _f32(params["wte"][tokens])
    chosen, selections = None, []
    for i, (p, kind) in enumerate(zip(params["blocks"], indexer)):
        x, chosen = _layer(
            x, chosen, p, full=kind == "full", dense=i < dense_layers,
            heads=heads, nope=nope, rope=rope, rank=rank,
            index_heads=index_heads, topk=topk, top_k=top_k,
            first_expert=first_expert, route_scale=route_scale, eps=eps,
            index_eps=index_eps, theta=theta)
        if kind == "full" and return_selected:
            selections.append(chosen)
    out = _head(x, params["ln_f"]["scale"], params["lm_head"], eps=eps)
    return (out, selections) if return_selected else out
