"""Plain reference for Nemotron-H (`model_type` `nemotron_h`, Nemotron 3
Nano): the forward pass in straightforward `jax.numpy`, float32, matmuls
at `highest` precision, no kernel, no cache, no batching, and of the
Mamba-2 layers THE RECURRENCE ONLY: a `lax.scan` over positions, no
chunks.  For one sequence x [S, D]:

  x = wte[token] (no scale, no positions anywhere).
  Layer l is ONE part under ONE norm, `hybrid_override_pattern[l]` says
  which:  x <- x + part_l(N(x)),  N(x) = w x / sqrt(mean(x^2) + eps).
  "M" Mamba-2 (d_in = heads x head_dim, G groups of `state` values,
  conv = d_in + 2 G state), u_t = N(x_t):
      [z_t | xBC_t | dt_t] = u_t W_in               (d_in | conv | heads)
      c_t = silu(b_c + sum_{j<K} w_c[:, j] xBC_{t-(K-1)+j})   zeros before 0
      [x_t | B_t | C_t] = c_t                       (d_in | G state | G state)
      D_t = softplus(dt_t + dt_bias) (no clamp), a_t = exp(D_t A),
      A = -exp(A_log);  head h reads group g = h // (heads / G):
      S_h <- a_t S_h + D_t x_h (x) B_g  [head_dim, state], S_{-1} = 0;
      y_h = S_h C_g + D_h x_h
      g_t = y_t silu(z_t);  out_t = G(g_t) W_out, G an RMS norm over each
      group's d_in / G values APART (the gate before the norm), a gain
      of d_in.
  "*" grouped attention: q, k, v without bias, query head n on K/V head
      n // (H / KV), NO rotation, causal softmax at head_dim^-1/2, Wo.
  "E" experts: s = sigmoid(u W_r) over all E; the top_k of s + b (b
      chooses and does not weigh; one group: no grouped top-k); weights
      s_i / (sum s_i + 1e-20) * routed_scaling_factor; an expert is
      W_d relu(W_u u)^2 (two matrices); plus the shared expert of the
      same form.  Behind a share of the experts only those held are
      computed: the partial result goes on, here as in the program.
  logits = N_f(x) lm_head over the rows of the vocabulary held.

Departures from the published modelling code: none in the equations.
The published attention applies no rotary embedding, so `rope_theta`,
`partial_rotary_factor` and `max_position_embeddings` size nothing
(`assumed.unused` in the configuration file); the published stream is
bf16 (`residual_in_fp32` false) where this one, as the program's, is
float32.

Weights are the system's own tree (`deepspeed_tpu.models.nemotron_h
.NemotronH.init`'s layout: `in` [D, d_in + conv + heads] with z first,
`conv_w` [conv, K], an expert's `up` [held, D, F] and `down`), upcast a
layer at a time inside the jitted layer, and the head a block of the
vocabulary at a time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_BLOCK = 4096      # most vocabulary columns of one head product
QUERY_BLOCK = 1024     # most queries of one block of scores
NEG_INF = -1e30


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` needs, from a configuration file."""
    return {"pattern": config["hybrid_override_pattern"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "ssm_heads": config["mamba_num_heads"],
            "state": config["ssm_state_size"],
            "groups": config["n_groups"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": config["held"]["first_expert"],
            "route_scale": float(config["routed_scaling_factor"]),
            "eps": config["layer_norm_epsilon"]}


def _f32(a):
    return a.astype(jnp.float32)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _mamba(u, p, *, ssm_heads, state, groups, eps):
    """u [S, D] float32 (one sequence, after the norm) -> the mixer's
    output [S, D], by the recurrence."""
    S = u.shape[0]
    w_c = _f32(p["conv_w"])                                  # [conv, K]
    conv, K = w_c.shape
    d_in = conv - 2 * groups * state
    P, per = d_in // ssm_heads, ssm_heads // groups
    z, xBC, dt = jnp.split(u @ _f32(p["in"]), [d_in, d_in + conv], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, conv)), xBC])
    c = _f32(p["conv_b"])
    for j in range(K):
        c = c + w_c[:, j] * padded[j:j + S]
    c = jax.nn.silu(c)
    x, Bm, Cm = jnp.split(c, [d_in, d_in + groups * state], axis=-1)
    x = x.reshape(S, ssm_heads, P)
    # head h reads group h // per: every head its own copy
    Bm = jnp.repeat(Bm.reshape(S, groups, state), per, axis=1)
    Cm = jnp.repeat(Cm.reshape(S, groups, state), per, axis=1)
    delta = jax.nn.softplus(dt + _f32(p["dt_bias"]))         # [S, heads]
    a = jnp.exp(delta * -jnp.exp(_f32(p["A_log"])))

    def token(H, t):
        x_t, B_t, C_t, d_t, a_t = t
        H = a_t[:, None, None] * H + \
            d_t[:, None, None] * x_t[:, :, None] * B_t[:, None, :]
        return H, jnp.einsum("hpn,hn->hp", H, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((ssm_heads, P, state)),
                        (x, Bm, Cm, delta, a))
    y = y + _f32(p["D"])[:, None] * x
    g = (y.reshape(S, d_in) * jax.nn.silu(z)).reshape(S, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(S, d_in) * _f32(p["norm"]["scale"])) @ _f32(p["out"])


def _attention(u, p, *, heads, kv_heads):
    """u [S, D] -> causal grouped attention without positions, [S, D]:
    a K/V head (its H / KV query heads) and a block of queries at a
    time."""
    S = u.shape[0]
    group = heads // kv_heads
    dh = p["k"].shape[1] // kv_heads
    pos = jnp.arange(S)
    q = (u @ _f32(p["q"])).reshape(S, kv_heads, group, dh)
    k = (u @ _f32(p["k"])).reshape(S, kv_heads, dh)
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


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ _f32(up))) @ _f32(down)


def _experts(h, p, *, top_k, first_expert, route_scale):
    """h [S, D] -> routed + shared: every held expert for every token,
    weighted by its renormalised, scaled sigmoid score where it is among
    the token's `top_k` of ALL the router's experts by score plus bias,
    by 0 elsewhere."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))                  # [S, E]
    biased = s + _f32(p["select_bias"])
    kth = jax.lax.top_k(biased, top_k)[0][..., -1:]
    # greedy top-k keeps the first of equal scores; scores of seeded
    # float32 weights do not tie exactly
    chosen = jnp.where(biased >= kth, s, 0.0)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) \
        * route_scale
    e = p["experts"]
    w_held = jax.lax.dynamic_slice_in_dim(w, first_expert,
                                          e["up"].shape[0], axis=1)

    def one(acc, args):
        up, down, we = args
        return acc + we[:, None] * _relu2(h, up, down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (e["up"], e["down"], w_held.T))
    return routed + _relu2(h, p["shared"]["up"], p["shared"]["down"])


@functools.partial(jax.jit, static_argnames=(
    "part", "heads", "kv_heads", "ssm_heads", "state", "groups", "top_k",
    "first_expert", "route_scale", "eps"))
def _layer(x, p, *, part, heads, kv_heads, ssm_heads, state, groups, top_k,
           first_expert, route_scale, eps):
    """x [B, S, D] float32 -> one layer on, a sequence at a time."""
    with jax.default_matmul_precision(HIGHEST):
        def one(xs):
            u = _norm(xs, p["ln1"]["scale"], eps)
            if part == "M":
                return xs + _mamba(u, p["ssm"], ssm_heads=ssm_heads,
                                   state=state, groups=groups, eps=eps)
            if part == "*":
                return xs + _attention(u, p["attn"], heads=heads,
                                       kv_heads=kv_heads)
            return xs + _experts(u, p["mlp"], top_k=top_k,
                                 first_expert=first_expert,
                                 route_scale=route_scale)

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, w, *, eps):
    """Final norm and the untied head, a block of the vocabulary at a
    time, written into the one [B, S, V] array."""
    with jax.default_matmul_precision(HIGHEST):
        h = _norm(x, g, eps)
        V = w.shape[1]
        blk = _divisor(V, HEAD_BLOCK)

        def one(i, out):
            cols = jax.lax.dynamic_slice_in_dim(w, i * blk, blk, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, h @ _f32(cols), i * blk, axis=2)

        return jax.lax.fori_loop(
            0, V // blk, one, jnp.zeros(x.shape[:2] + (V,), jnp.float32))


def stages(params, tokens, *, pattern, **kw):
    """The stream after the embedding and after every layer, one layer
    at a time: a generator of [B, S, D] float32."""
    x = _f32(params["wte"][tokens])
    yield x
    for p, part in zip(params["blocks"], pattern):
        x = _layer(x, p, part=part, **kw)
        yield x


def logits(params, tokens, *, eps, **kw):
    """tokens [B, S] int32 -> [B, S, V] float32 over the rows of the
    vocabulary held."""
    for x in stages(params, tokens, eps=eps, **kw):
        pass
    return _head(x, params["ln_f"]["scale"], params["lm_head"], eps=eps)
