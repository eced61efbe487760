"""Plain reference for Granite 4.0-H (`model_type` `granitemoehybrid`,
dense): the forward pass in straightforward `jax.numpy`, float32, matmuls
at `highest` precision, no kernel, no cache, no batching, and of the
state-space layers THE RECURRENCE ONLY: a `lax.scan` over positions, no
chunks.  For one sequence x [S, D]:

  x = embedding_multiplier * wte[token].  No positions anywhere.
  Every layer:  x <- x + r mixer(RMSNorm(x)),  x <- x + r MLP(RMSNorm(x)),
      r = residual_multiplier, RMSNorm(x) = w x / sqrt(mean(x^2) + eps),
      MLP(h) = (silu(h Wg) * h Wu) Wd.
  Mamba-2 mixer (`layer_types[l] == "mamba"`; d_in = heads x head_dim,
  conv = d_in + 2 state, one group), u_t = RMSNorm(x_t):
      [z_t | xBC_t | dt_t] = u_t W_in               (d_in | conv | heads)
      c_t = silu(b_c + sum_{j<K} w_c[:, j] xBC_{t-(K-1)+j})   zeros before 0
      [x_t | B_t | C_t] = c_t                       (d_in | state | state)
      D_t = softplus(dt_t + dt_bias), a_t = exp(D_t A), A = -exp(A_log)
      H_t = a_t H_{t-1} + D_t x_t (x) B_t  per head [head_dim, state],
      H_{-1} = 0;  y_t = H_t C_t + D x_t   (B, C shared by the heads)
      g_t = y_t silu(z_t);  out_t = (w_n g_t / sqrt(mean(g_t^2) + eps)) W_out
      (the gate BEFORE the norm, the norm over all d_in)
  Attention (`layer_types[l] == "attention"`): q, k, v without bias,
      query head n on K/V head n // (H / KV), NO rotation, scores
      attention_multiplier * q.k (not head_dim^-1/2), causal softmax, Wo.
  logits = RMSNorm(x) wte^T / logits_scaling  (tied).

Departures: none from the equations above; they are the issue's, read off
the public config.json and the Mamba-2 paper's recurrence.  What the
config does not give (the state's dtype in a cache, the seeded ranges of
A and dt) concerns the system and the seeded weights only and is listed
under `assumed` in the configuration file.

Weights are the system's own tree (`deepspeed_tpu.models.GraniteHybrid
.init`'s layout: `in` [D, d_in + conv + heads] with z first, `conv_w`
[conv, K], gate and up apart), upcast a layer at a time inside the jitted
layer — the float32 copy of one layer (0.3 GB at the published widths)
stands beside the engine's weights and state, never the model's — and
the head a block of the vocabulary at a time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_BLOCK = 4096      # most vocabulary rows of one head product
NEG_INF = -1e30


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` needs, from a configuration file."""
    return {"mixers": tuple(config["layer_types"]
                            [:config["num_hidden_layers"]]),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "ssm_heads": config["mamba_n_heads"],
            "state": config["mamba_d_state"],
            "eps": config["rms_norm_eps"],
            "embed": float(config["embedding_multiplier"]),
            "residual": float(config["residual_multiplier"]),
            "attn": float(config["attention_multiplier"]),
            "divisor": float(config["logits_scaling"])}


def _f32(a):
    return a.astype(jnp.float32)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _mamba(u, p, *, ssm_heads, state, eps):
    """u [S, D] float32 (one sequence, after the norm) -> the mixer's
    output [S, D], by the recurrence."""
    S = u.shape[0]
    w_c = _f32(p["conv_w"])                                  # [conv, K]
    conv, K = w_c.shape
    d_in = conv - 2 * state
    P = d_in // ssm_heads
    z, xBC, dt = jnp.split(u @ _f32(p["in"]), [d_in, d_in + conv], axis=-1)
    # c_t = silu(b + sum_j w[:, j] xBC_{t-(K-1)+j}), zeros before 0
    padded = jnp.concatenate([jnp.zeros((K - 1, conv)), xBC])
    c = _f32(p["conv_b"])
    for j in range(K):
        c = c + w_c[:, j] * padded[j:j + S]
    c = jax.nn.silu(c)
    x, Bm, Cm = jnp.split(c, [d_in, d_in + state], axis=-1)
    x = x.reshape(S, ssm_heads, P)
    delta = jax.nn.softplus(dt + _f32(p["dt_bias"]))         # [S, heads]
    a = jnp.exp(delta * -jnp.exp(_f32(p["A_log"])))

    def token(H, t):
        x_t, B_t, C_t, d_t, a_t = t
        H = a_t[:, None, None] * H + \
            d_t[:, None, None] * x_t[:, :, None] * B_t[None, None, :]
        return H, H @ C_t                                    # [heads, P]

    _, y = jax.lax.scan(token, jnp.zeros((ssm_heads, P, state)),
                        (x, Bm, Cm, delta, a))
    y = y + _f32(p["D"])[:, None] * x
    g = y.reshape(S, d_in) * jax.nn.silu(z)
    return _rms_norm(g, p["norm"]["scale"], eps) @ _f32(p["out"])


def _attention(u, p, *, heads, kv_heads, scale):
    """u [S, D] -> causal grouped attention without positions, [S, D]:
    a K/V head (its H / KV query heads) at a time."""
    S = u.shape[0]
    group = heads // kv_heads
    dh = p["k"].shape[1] // kv_heads
    q = (u @ _f32(p["q"])).reshape(S, kv_heads, group, dh)
    k = (u @ _f32(p["k"])).reshape(S, kv_heads, dh)
    v = (u @ _f32(p["v"])).reshape(S, kv_heads, dh)
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def kv_head(args):
        q_n, k_n, v_n = args             # [S, group, dh], [S, dh], [S, dh]
        s = jnp.einsum("qgd,kd->gqk", q_n, k_n) * scale
        pr = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
        return jnp.einsum("gqk,kd->qgd", pr, v_n)

    out = jax.lax.map(kv_head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                                jnp.moveaxis(v, 1, 0)))  # [KV, S, group, dh]
    return jnp.moveaxis(out, 0, 1).reshape(S, heads * dh) @ _f32(p["o"])


@functools.partial(jax.jit, static_argnames=(
    "mixer", "heads", "kv_heads", "ssm_heads", "state", "eps", "residual",
    "attn"))
def _layer(x, p, *, mixer, heads, kv_heads, ssm_heads, state, eps, residual,
           attn):
    """x [B, S, D] float32 -> one layer on, a sequence at a time."""
    with jax.default_matmul_precision(HIGHEST):
        def one(xs):
            u = _rms_norm(xs, p["ln1"]["scale"], eps)
            if mixer == "mamba":
                mixed = _mamba(u, p["ssm"], ssm_heads=ssm_heads, state=state,
                               eps=eps)
            else:
                mixed = _attention(u, p["attn"], heads=heads,
                                   kv_heads=kv_heads, scale=attn)
            xs = xs + residual * mixed
            u = _rms_norm(xs, p["ln2"]["scale"], eps)
            m = p["mlp"]
            return xs + residual * (
                (jax.nn.silu(u @ _f32(m["gate"])) * (u @ _f32(m["up"])))
                @ _f32(m["down"]))

        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps", "divisor"))
def _head(x, w, wte, *, eps, divisor):
    """Final norm and the tied head over `divisor`, a block of the
    vocabulary at a time, written into the one [B, S, V] array."""
    with jax.default_matmul_precision(HIGHEST):
        h = _rms_norm(x, w, eps)
        V = wte.shape[0]
        blk = _divisor(V, HEAD_BLOCK)

        def one(i, out):
            rows = jax.lax.dynamic_slice_in_dim(wte, i * blk, blk, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                out, h @ _f32(rows).T / divisor, i * blk, axis=2)

        return jax.lax.fori_loop(
            0, V // blk, one, jnp.zeros(x.shape[:2] + (V,), jnp.float32))


def logits(params, tokens, *, mixers, heads, kv_heads, ssm_heads, state,
           eps, embed, residual, attn, divisor):
    """tokens [B, S] int32 -> [B, S, V] float32."""
    x = _f32(params["wte"][tokens]) * embed
    for p, mixer in zip(params["blocks"], mixers):
        x = _layer(x, p, mixer=mixer, heads=heads, kv_heads=kv_heads,
                   ssm_heads=ssm_heads, state=state, eps=eps,
                   residual=residual, attn=attn)
    return _head(x, params["ln_f"]["scale"], params["wte"], eps=eps,
                 divisor=divisor)
