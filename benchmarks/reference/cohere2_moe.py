"""Plain reference for Command A+ (`model_type` `cohere2_moe`): the
forward pass in straightforward `jax.numpy`, float32, matmuls at
`highest` precision, no kernel, no cache, no ring, no grouping of tokens
by expert.  For x [T, D] in layer l:

  h = LayerNorm(x; g, eps): mean subtracted, over sqrt(var + eps), times
      the gain g, no bias.  ONE norm a layer (the parallel block).
  q = h Wq -> H heads of dh;  k = h Wk, v = h Wv -> KV heads of dh; no
      bias, no q/k norm.  Query head n attends K/V head n // (H / KV).
  Sliding layers (l mod 4 in {0, 1, 2}): q and k rotated over the whole
      head, GPT-J pairing (dims 2i and 2i + 1 by p theta^(-2i/dh)); the
      query at p attends keys j with p - window < j <= p.
  Full layers (l mod 4 = 3): no positions at all, causal.
  score = q.k dh^-1/2, softmax, a = (sum p v, heads side by side) Wo.
  On the same h: s = sigmoid(h Wr) over the E routed experts, the
      `top_k` largest, w_i = s_i / sum of the chosen s;
      routed = sum_i w_i E_i(h), E_i a SiLU-gated FFN; shared = the MEAN
      of the S shared experts' outputs (gated FFNs of the same width).
  x <- x + a + routed + shared.
  Final LayerNorm, logits = x wte^T (tied, logit_scale 1).

The share a chip holds: `first_expert` and the number of expert matrices
in the tree say which routed experts are here (experts `first_expert` ..
`first_expert + held - 1`); the router keeps all E outputs and the
weights are normalised over all `top_k` chosen, and what the absent
experts would add is left out.  `wte` is the rows of the vocabulary held.

Departures (listed under `assumed` in the configuration file; the system
makes the same choices): "average" is read as the mean of the shared
experts' outputs added to the routed sum; the window counts the query's
own position; no routing bias.  Every held expert is computed for every
token and weighted by w where chosen, 0 elsewhere.

Weights are the system's own tree (`deepspeed_tpu.models.Cohere2Moe.init`'s
layout: the shared experts' matrices side by side, `gate` and `up`
[D, S F], `down` [S F, D]), upcast a piece at a time inside the jitted
pieces: attention a K/V head and a block of queries at a time, the
experts one at a time, the head a block of the vocabulary at a time — at
the timed sizes the reference runs beside the engine's weights and
pools, so its float32 copies stay under 1 GB at 16,384 positions."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_BLOCK = 4096      # most vocabulary rows of one head product
QUERY_BLOCK = 256      # most queries of one K/V head's scores at a time
TOKEN_BLOCK = 4096     # most tokens of one expert product
NEG_INF = -1e30


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` needs, from a configuration file."""
    windows = tuple(config["sliding_window"] if t == "sliding_attention"
                    else 0 for t in config["layer_types"])
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "top_k": config["num_experts_per_tok"],
            "shared": config["num_shared_experts"],
            "first_expert": config["held"]["first_expert"],
            "windows": windows[:config["num_hidden_layers"]],
            "eps": config["layer_norm_eps"],
            "theta": float(config["rope_theta"])}


def _f32(a):
    return a.astype(jnp.float32)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _layer_norm(x, g, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(g)


def _rope_gptj(x, pos, theta):
    """x [S, n, dh] at positions pos [S]: dims 2i and 2i + 1 rotated by
    pos theta^(-2i/dh)."""
    dh = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * \
        theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(h, p, *, heads, kv_heads, window, theta):
    """h [S, D] float32 (one sequence, after the norm) -> attention's
    output [S, D]: a K/V head (its H / KV query heads) at a time, a block
    of queries at a time."""
    S, D = h.shape
    group = heads // kv_heads
    dh = p["k"].shape[1] // kv_heads
    pos = jnp.arange(S)
    qb = _divisor(S, QUERY_BLOCK)

    def kv_head(acc, g):
        wq = jax.lax.dynamic_slice_in_dim(p["q"], g * group * dh,
                                          group * dh, axis=1)
        wk = jax.lax.dynamic_slice_in_dim(p["k"], g * dh, dh, axis=1)
        wv = jax.lax.dynamic_slice_in_dim(p["v"], g * dh, dh, axis=1)
        wo = jax.lax.dynamic_slice_in_dim(p["o"], g * group * dh,
                                          group * dh, axis=0)
        q = (h @ _f32(wq)).reshape(S, group, dh)
        k = (h @ _f32(wk)).reshape(S, 1, dh)
        v = h @ _f32(wv)                                       # [S, dh]
        if window:                    # sliding layers rotate; full do not
            q, k = _rope_gptj(q, pos, theta), _rope_gptj(k, pos, theta)
        k = k[:, 0]

        def block(i):
            at = i * qb
            qi = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=0)
            pq = at + jnp.arange(qb)
            seen = pos[None, :] <= pq[:, None]
            if window:
                seen &= pos[None, :] > pq[:, None] - window
            s = jnp.einsum("qnd,kd->nqk", qi, k) * dh ** -0.5
            pr = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
            return jnp.einsum("nqk,kd->qnd", pr, v).reshape(qb, group * dh)

        o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, group * dh)
        return acc + o @ _f32(wo), None

    out, _ = jax.lax.scan(kv_head, jnp.zeros_like(h), jnp.arange(kv_heads))
    return out


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _ffn(h, p, *, top_k, shared, first_expert):
    """h [S, D] -> routed + shared, a block of tokens at a time."""
    tb = _divisor(h.shape[0], TOKEN_BLOCK)
    return jax.lax.map(
        lambda hb: _ffn_block(hb, p, top_k=top_k, shared=shared,
                              first_expert=first_expert),
        h.reshape(-1, tb, h.shape[1])).reshape(h.shape)


def _ffn_block(h, p, *, top_k, shared, first_expert):
    """h [T, D] -> routed + shared: every held expert for every token,
    weighted by its renormalised sigmoid score where it is among the
    token's `top_k` of ALL the router's experts, by 0 elsewhere; the mean
    of the shared experts once."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))                  # [S, E]
    kth = jax.lax.top_k(s, top_k)[0][..., -1:]
    # greedy top-k keeps the first of equal scores; scores of seeded
    # float32 weights do not tie exactly
    chosen = jnp.where(s >= kth, s, 0.0)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    e = p["experts"]
    held = e["gate"].shape[0]
    w_held = jax.lax.dynamic_slice_in_dim(w, first_expert, held, axis=1)

    def one(acc, args):
        gate, up, down, we = args
        return acc + we[:, None] * _gated(h, gate, up, down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (e["gate"], e["up"], e["down"], w_held.T))
    sh = p["shared"]
    width = sh["gate"].shape[1] // shared

    def one_shared(acc, i):
        cols = lambda m: jax.lax.dynamic_slice_in_dim(m, i * width, width,
                                                      axis=1)
        down = jax.lax.dynamic_slice_in_dim(sh["down"], i * width, width,
                                            axis=0)
        return acc + _gated(h, cols(sh["gate"]), cols(sh["up"]), down), None

    total, _ = jax.lax.scan(one_shared, jnp.zeros_like(h), jnp.arange(shared))
    return routed + total / shared


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "top_k", "shared", "first_expert", "eps",
    "theta"))
def _layer(x, p, *, heads, kv_heads, window, top_k, shared, first_expert,
           eps, theta):
    """x [B, S, D] float32 -> x + attention(h) + ffn(h), h the one norm."""
    with jax.default_matmul_precision(HIGHEST):
        def one(xs):
            h = _layer_norm(xs, p["ln1"]["scale"], eps)
            return xs + _attention(h, p["attn"], heads=heads,
                                   kv_heads=kv_heads, window=window,
                                   theta=theta) + \
                _ffn(h, p["mlp"], top_k=top_k, shared=shared,
                     first_expert=first_expert)

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, wte, *, eps):
    """Final norm and the tied head, a block of the vocabulary at a time,
    written into the one [B, S, V] array."""
    with jax.default_matmul_precision(HIGHEST):
        h = _layer_norm(x, g, eps)
        V = wte.shape[0]
        blk = _divisor(V, HEAD_BLOCK)
        n = V // blk

        def one(i, out):
            rows = jax.lax.dynamic_slice_in_dim(wte, i * blk, blk, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                out, h @ _f32(rows).T, i * blk, axis=2)

        return jax.lax.fori_loop(
            0, n, one, jnp.zeros(x.shape[:2] + (V,), jnp.float32))


def logits(params, tokens, *, heads, kv_heads, top_k, shared, first_expert,
           windows, eps, theta):
    """tokens [B, S] int32 -> [B, S, V] float32 over the rows of the
    vocabulary held."""
    x = _f32(params["wte"][tokens])
    for p, window in zip(params["blocks"], windows):
        x = _layer(x, p, heads=heads, kv_heads=kv_heads, window=window,
                   top_k=top_k, shared=shared, first_expert=first_expert,
                   eps=eps, theta=theta)
    return _head(x, params["ln_f"]["scale"], params["wte"], eps=eps)
