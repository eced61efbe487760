"""Plain reference for Qwen3-Next (`model_type` `qwen3_next`): the forward
pass in straightforward `jax.numpy`, float32, matmuls at `highest`
precision, no kernel, no cache, no batching, no grouping of tokens by
expert, and of the gated delta layers THE RECURRENCE ONLY: a `lax.scan`
over positions, no chunks.  For one sequence x [S, D]
(N(x) = x rsqrt(mean(x^2) + eps) (1 + w), residual float32):

  x = wte[token].  Layer l: x <- x + mixer(N1 x), x <- x + moe(N2 x);
  full attention where (l + 1) % period == 0, else gated delta.
  Gated delta mixer (Hk key heads of dk, Hv value heads of dv; value head
  j on key head j // (Hv / Hk)), h = N1 x:
      [q | k | v | z] = h W_qkvz          (Hk dk | Hk dk | Hv dv | Hv dv)
      [b | a] = h W_ba                    (Hv | Hv)
      [q | k | v] <- silu(sum_{j<K} w_c[:, j] [q | k | v]_{t-(K-1)+j}),
          zeros before 0, no bias
      q^ = q / sqrt(|q|^2 + 1e-6) dk^-1/2,  k^ = k / sqrt(|k|^2 + 1e-6)
      beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
      S_t = e^g S_{t-1};  u = beta (v - S_t^T k^);  S_t += k^ u^T;
      o = S_t^T q^                        per value head, S_{-1} = 0
      out = (w_n o / sqrt(mean(o^2) + eps) * silu(z)) W_o
          (the norm over a head's dv, a plain gain, BEFORE the gate)
  Gated attention: [q | gate] a head = h W_q (H heads of 2 Dh), k, v =
      h W_k, h W_v (KV heads of Dh); q <- N(q), k <- N(k) over the head;
      the first `rotary` values of a head rotated, dims i and
      i + rotary / 2 by p theta^(-2i/rotary); causal softmax at Dh^-1/2,
      query head n on K/V head n // (H / KV); out = (attn *
      sigmoid(gate)) W_o.  No biases.
  Experts: p = softmax(h W_r) over ALL the router's outputs; the `top_k`
      largest, weights over their sum; y = sum_i w_i E_i(h) +
      sigmoid(h w_s) Shared(h), every one a SiLU-gated FFN.
  Final N, logits = x W_head (untied).

The share a chip holds: `first_expert` and the number of expert matrices
in the tree say which routed experts are here; the router keeps all its
outputs and the weights are normalised over all `top_k` chosen, and what
the absent experts would add is left out.  `wte` and `lm_head` are the
rows of the vocabulary held.

Departures (listed under `assumed` in the configuration file; the system
makes the same choices): W_qkvz's columns are [q | k | v | z] with heads
side by side (the published checkpoint interleaves them by key head: a
column permutation under seeded weights); the multi-token-prediction
layer is left out.

Weights are the system's own tree (`deepspeed_tpu.models.qwen3_next.
Qwen3Next.init`'s layout), upcast a piece at a time inside the jitted
pieces: attention a K/V head and a block of queries at a time, the
experts one at a time, the head a block of the vocabulary at a time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_BLOCK = 4096      # most vocabulary rows of one head product
QUERY_BLOCK = 1024     # most queries of one K/V head's scores at a time
NEG_INF = -1e30


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` needs, from a configuration file."""
    period = config["full_attention_interval"]
    layers = config["held"]["layers"]
    return {"attends": tuple((i + 1) % period == 0 for i in layers),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "rotary": int(config["partial_rotary_factor"]
                          * config["head_dim"]),
            "theta": float(config["rope_theta"]),
            "key_heads": config["linear_num_key_heads"],
            "value_heads": config["linear_num_value_heads"],
            "key_dim": config["linear_key_head_dim"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": config["held"]["first_expert"],
            "eps": config["rms_norm_eps"]}


def _f32(a):
    return a.astype(jnp.float32)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _norm(x, w, eps):
    """RMSNorm with the scale 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + _f32(w))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta(h, p, *, key_heads, value_heads, key_dim, eps):
    """h [S, D] float32 (one sequence, after the norm) -> the mixer's
    output [S, D], by the recurrence."""
    S = h.shape[0]
    w_c = _f32(p["conv_w"])                                  # [conv, K]
    conv, K = w_c.shape
    kw = key_heads * key_dim
    vw = conv - 2 * kw
    dv, rep = vw // value_heads, value_heads // key_heads
    qkv, z = jnp.split(h @ _f32(p["qkvz"]), [conv], axis=-1)
    b, a = jnp.split(h @ _f32(p["ba"]), 2, axis=-1)          # [S, Hv]
    padded = jnp.concatenate([jnp.zeros((K - 1, conv)), qkv])
    c = jax.nn.silu(sum(w_c[:, j] * padded[j:j + S] for j in range(K)))
    q, k, v = jnp.split(c, [kw, 2 * kw], axis=-1)
    heads = lambda t: jnp.repeat(t.reshape(S, key_heads, key_dim), rep,
                                 axis=1)                      # [S, Hv, dk]
    q, k = _l2(heads(q)) * key_dim ** -0.5, _l2(heads(k))
    v = v.reshape(S, value_heads, dv)
    beta = jax.nn.sigmoid(b)
    decay = jnp.exp(-jnp.exp(_f32(p["A_log"]))
                    * jax.nn.softplus(a + _f32(p["dt_bias"])))

    def token(state, t):                       # state [Hv, dk, dv]
        q_t, k_t, v_t, beta_t, decay_t = t
        state = decay_t[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((value_heads, key_dim, dv)),
                        (q, k, v, beta, decay))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * _f32(p["norm"]["scale"])
    return (o.reshape(S, vw) * jax.nn.silu(z)) @ _f32(p["out"])


def _rope_halves(x, pos, theta):
    """x [S, n, r] rotated over all r values, dims i and i + r / 2."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None, None] * inv       # [S, 1, r/2]
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _attention(h, p, *, heads, kv_heads, rotary, theta, eps):
    """h [S, D] -> gated causal grouped attention, [S, D]: a K/V head
    (its H / KV query heads) and a block of queries at a time."""
    S = h.shape[0]
    group = heads // kv_heads
    dh = p["k"].shape[1] // kv_heads
    pos = jnp.arange(S)
    qg = (h @ _f32(p["q"])).reshape(S, heads, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:].reshape(S, heads * dh)
    k = (h @ _f32(p["k"])).reshape(S, kv_heads, dh)
    v = (h @ _f32(p["v"])).reshape(S, kv_heads, dh)
    q = _norm(q, p["q_norm"]["scale"], eps)
    k = _norm(k, p["k_norm"]["scale"], eps)
    turn = lambda t: jnp.concatenate(
        [_rope_halves(t[..., :rotary], pos, theta), t[..., rotary:]], -1)
    q, k = turn(q).reshape(S, kv_heads, group, dh), turn(k)
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
    out = jnp.moveaxis(out, 0, 1).reshape(S, heads * dh)
    return (out * jax.nn.sigmoid(gate)) @ _f32(p["o"])


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _moe(h, p, *, top_k, first_expert):
    """h [S, D] -> routed + gated shared: every held expert for every
    token, weighted by its renormalised softmax score where it is among
    the token's `top_k` of ALL the router's experts, by 0 elsewhere."""
    s = jax.nn.softmax(h @ _f32(p["router"]), axis=-1)         # [S, E]
    kth = jax.lax.top_k(s, top_k)[0][..., -1:]
    # greedy top-k keeps the first of equal scores; scores of seeded
    # float32 weights do not tie exactly
    chosen = jnp.where(s >= kth, s, 0.0)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    e = p["experts"]
    w_held = jax.lax.dynamic_slice_in_dim(w, first_expert,
                                          e["gate"].shape[0], axis=1)

    def one(acc, args):
        gate, up, down, we = args
        return acc + we[:, None] * _gated(h, gate, up, down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (e["gate"], e["up"], e["down"], w_held.T))
    sh = p["shared"]
    return routed + jax.nn.sigmoid(h @ _f32(p["shared_gate"])) * \
        _gated(h, sh["gate"], sh["up"], sh["down"])


@functools.partial(jax.jit, static_argnames=(
    "attends", "heads", "kv_heads", "rotary", "theta", "key_heads",
    "value_heads", "key_dim", "top_k", "first_expert", "eps"))
def _layer(x, p, *, attends, heads, kv_heads, rotary, theta, key_heads,
           value_heads, key_dim, top_k, first_expert, eps):
    """x [B, S, D] float32 -> one layer on, a sequence at a time."""
    with jax.default_matmul_precision(HIGHEST):
        def one(xs):
            h = _norm(xs, p["ln1"]["scale"], eps)
            if attends:
                xs = xs + _attention(h, p["attn"], heads=heads,
                                     kv_heads=kv_heads, rotary=rotary,
                                     theta=theta, eps=eps)
            else:
                xs = xs + _delta(h, p["gdn"], key_heads=key_heads,
                                 value_heads=value_heads, key_dim=key_dim,
                                 eps=eps)
            h = _norm(xs, p["ln2"]["scale"], eps)
            return xs + _moe(h, p["mlp"], top_k=top_k,
                             first_expert=first_expert)

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


def logits(params, tokens, *, attends, heads, kv_heads, rotary, theta,
           key_heads, value_heads, key_dim, top_k, first_expert, eps):
    """tokens [B, S] int32 -> [B, S, V] float32 over the rows of the
    vocabulary held."""
    x = _f32(params["wte"][tokens])
    for p, a in zip(params["blocks"], attends):
        x = _layer(x, p, attends=a, heads=heads, kv_heads=kv_heads,
                   rotary=rotary, theta=theta, key_heads=key_heads,
                   value_heads=value_heads, key_dim=key_dim, top_k=top_k,
                   first_expert=first_expert, eps=eps)
    return _head(x, params["ln_f"]["scale"], params["lm_head"], eps=eps)
