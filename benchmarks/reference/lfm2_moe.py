"""Plain reference for LFM2-MoE (`model_type` `lfm2_moe`, LFM2-24B-A2B):
the forward pass in straightforward `jax.numpy`, float32, matmuls at
`highest` precision, no kernel, no cache, no batching, no kept rows: the
convolution runs over the whole sequence from zeros.  For one sequence
x [S, D]:

  x = wte[token] (no scale).
  N(x) = w x / sqrt(mean(x^2) + eps), a plain gain (`norm_eps`).
  Layer l:  x <- x + mixer_l(N1_l x);  x <- x + ffn_l(N2_l x)
  (`operator_norm`, `ffn_norm`; the sequential residual).
  `layer_types[l]` "conv": the gated short convolution, u = N1 x:
      [B_t | C_t | u_t] = u_t W_in                  (D | D | D)
      g_t = B_t * u_t
      c_t = sum_{j<K} w[:, j] g_{t-(K-1)+j}         K = conv_L_cache taps,
                                                    causal, depthwise, no
                                                    bias, zeros before 0
      out_t = (C_t * c_t) W_out
    No activation, no recurrence, no positions.
  "full_attention": q = u W_q (H heads of dh), k, v = u W_k, u W_v (KV
      heads of dh); q and k RMS-normed over the head (`q_layernorm`,
      `k_layernorm`: eps `norm_eps`, a plain gain of dh), then rotated
      over the WHOLE head, pairs i and i + dh / 2 (`rotate_half`),
      theta `rope_theta`, no scaling; causal softmax at dh^-1/2, query
      head n on K/V head n // (H / KV); W_o.
  FFN: layers 0 .. num_dense_layers - 1  W_2 (silu(W_1 u) * W_3 u) at
      `intermediate_size`; the others route: s = sigmoid(u W_r) over all
      E in float32; the top_k of s + b (b, `expert_bias`, chooses and
      does not weigh); weights s_i / (sum s_i + 1e-6) *
      routed_scaling_factor (`norm_topk_prob`); an expert is
      W_2 (silu(W_1 u) * W_3 u) at `moe_intermediate_size`; NO shared
      expert.  Behind a share of the experts only those held are
      computed: the partial result goes on, here as in the program.
  logits = N_f(x) wte^T over the rows of the vocabulary held
  (`embedding_norm`; the head is tied: `assumed` in the configuration).

Departures from the published modelling code, each under `assumed` in
the configuration file: the router's scores and the stream are float32
(published: the model's dtype), and 1e-6 in the renormalisation is the
published block's as recalled, which the config cannot say.

Weights are the system's own tree (`deepspeed_tpu.models.lfm2_moe
.Lfm2Moe.init`'s layout: a conv layer's `in` [D, 3 D] with B first,
`conv_w` [D, K] with the newest position's tap last, an expert's `gate`,
`up` [held, D, F] and `down`), upcast a layer at a time inside the
jitted layer, and the head a block of the vocabulary at a time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_BLOCK = 4096      # most vocabulary columns of one head product
QUERY_BLOCK = 1024     # most queries of one block of scores
NEG_INF = -1e30
RENORM_EPS = 1e-6


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` needs, from a configuration file."""
    return {"layer_types": tuple(config["layer_types"]),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": config["held"]["first_expert"],
            "route_scale": float(config["routed_scaling_factor"]),
            "theta": float(config["rope_parameters"]["rope_theta"]),
            "eps": config["norm_eps"]}


def _f32(a):
    return a.astype(jnp.float32)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _conv(u, p):
    """u [S, D] float32 (one sequence, after the norm) -> the gated
    short convolution's output [S, D]."""
    S = u.shape[0]
    w = _f32(p["conv_w"])                                    # [D, K]
    K = w.shape[1]
    gate_in, gate_out, v = jnp.split(u @ _f32(p["in"]), 3, axis=-1)
    g = jnp.concatenate([jnp.zeros((K - 1, w.shape[0])), gate_in * v])
    c = jnp.zeros_like(v)
    for j in range(K):
        c = c + w[:, j] * g[j:j + S]
    return (gate_out * c) @ _f32(p["out"])


def _rotate(x, theta):
    """x [S, n, dh] at positions 0 .. S - 1: pairs i, i + dh / 2."""
    S, dh = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv    # [S, dh/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def _attention(u, p, *, heads, kv_heads, theta, eps):
    """u [S, D] -> causal grouped attention with normed, rotated q and
    k, [S, D]: a K/V head (its H / KV query heads) and a block of
    queries at a time."""
    S = u.shape[0]
    group = heads // kv_heads
    dh = p["k"].shape[1] // kv_heads
    pos = jnp.arange(S)
    q = _norm((u @ _f32(p["q"])).reshape(S, heads, dh),
              p["q_norm"]["scale"], eps)
    k = _norm((u @ _f32(p["k"])).reshape(S, kv_heads, dh),
              p["k_norm"]["scale"], eps)
    q = _rotate(q, theta).reshape(S, kv_heads, group, dh)
    k = _rotate(k, theta)
    v = (u @ _f32(p["v"])).reshape(S, kv_heads, dh)
    qb = _divisor(S, QUERY_BLOCK)

    def kv_head(args):
        q_n, k_n, v_n = args             # [S, group, dh], [S, dh], [S, dh]

        def block(i):
            q_i = jax.lax.dynamic_slice_in_dim(q_n, i * qb, qb, axis=0)
            s = jnp.einsum("qgd,kd->gqk", q_i, k_n) * dh ** -0.5
            seen = pos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
            pr = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
            return jnp.einsum("gqk,kd->qgd", pr, v_n)

        return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, group, dh)

    out = jax.lax.map(kv_head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                                jnp.moveaxis(v, 1, 0)))  # [KV, S, group, dh]
    return jnp.moveaxis(out, 0, 1).reshape(S, heads * dh) @ _f32(p["o"])


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _experts(h, p, *, top_k, first_expert, route_scale):
    """h [S, D] -> every held expert for every token, weighted by its
    renormalised, scaled sigmoid score where it is among the token's
    `top_k` of ALL the router's experts by score plus bias, by 0
    elsewhere."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))                  # [S, E]
    biased = s + _f32(p["select_bias"])
    kth = jax.lax.top_k(biased, top_k)[0][..., -1:]
    # greedy top-k keeps the first of equal scores; scores of seeded
    # float32 weights do not tie exactly
    chosen = jnp.where(biased >= kth, s, 0.0)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + RENORM_EPS) \
        * route_scale
    e = p["experts"]
    w_held = jax.lax.dynamic_slice_in_dim(w, first_expert,
                                          e["up"].shape[0], axis=1)

    def one(acc, args):
        gate, up, down, we = args
        return acc + we[:, None] * _gated(h, gate, up, down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (e["gate"], e["up"], e["down"], w_held.T))
    return routed


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "kv_heads", "top_k", "first_expert", "route_scale",
    "theta", "eps"))
def _layer(x, p, *, kind, heads, kv_heads, top_k, first_expert, route_scale,
           theta, eps):
    """x [B, S, D] float32 -> one layer on, a sequence at a time."""
    with jax.default_matmul_precision(HIGHEST):
        def one(xs):
            u = _norm(xs, p["ln1"]["scale"], eps)
            if kind == "conv":
                xs = xs + _conv(u, p["conv"])
            else:
                xs = xs + _attention(u, p["attn"], heads=heads,
                                     kv_heads=kv_heads, theta=theta, eps=eps)
            u = _norm(xs, p["ln2"]["scale"], eps)
            mlp = p["mlp"]
            if "router" not in mlp:          # a leading dense layer
                return xs + _gated(u, mlp["gate"], mlp["up"], mlp["down"])
            return xs + _experts(u, mlp, top_k=top_k,
                                 first_expert=first_expert,
                                 route_scale=route_scale)

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, wte, *, eps):
    """Final norm and the tied head, a block of the vocabulary at a
    time, written into the one [B, S, V] array."""
    with jax.default_matmul_precision(HIGHEST):
        h = _norm(x, g, eps)
        V = wte.shape[0]
        blk = _divisor(V, HEAD_BLOCK)

        def one(i, out):
            rows = jax.lax.dynamic_slice_in_dim(wte, i * blk, blk, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                out, h @ _f32(rows).T, i * blk, axis=2)

        return jax.lax.fori_loop(
            0, V // blk, one, jnp.zeros(x.shape[:2] + (V,), jnp.float32))


def stages(params, tokens, *, layer_types, **kw):
    """The stream after the embedding and after every layer, one layer
    at a time: a generator of [B, S, D] float32."""
    x = _f32(params["wte"][tokens])
    yield x
    for p, kind in zip(params["blocks"], layer_types):
        x = _layer(x, p, kind=kind, **kw)
        yield x


def logits(params, tokens, *, eps, **kw):
    """tokens [B, S] int32 -> [B, S, V] float32 over the rows of the
    vocabulary held."""
    for x in stages(params, tokens, eps=eps, **kw):
        pass
    return _head(x, params["ln_f"]["scale"], params["wte"], eps=eps)
