"""Plain reference for EvaByte: the forward pass in straightforward
`jax.numpy`, float32, matmuls at `highest` precision, no kernel, no
cache.  It follows the published description: EVA (Zheng et al.,
"Efficient Attention via Control Variates", ICLR 2023) in the
deterministic form the released model ships, behind pre-norm blocks with
unit-offset RMSNorm, rotary positions over the whole head and a
SiLU-gated FFN; logits of the first of the `num_pred_heads` output heads
(the next byte).

Per head, scale s = head_dim^-1/2, window W, chunk C.  A chunk's
summaries pool its own C keys and values with softmax weights from two
learned vectors mu, phi: k~_c = sum_j softmax_j(s k_j.mu) k_j and
v~_c = sum_j softmax_j(s k_j.phi) v_j.  Query i attends, in one softmax,
to the exact keys j <= i of its own window and to the summaries of every
chunk that lies wholly in an earlier window.

Departures (the three details `config.json` does not settle, listed under
`assumed` in the configuration file; the system makes the same choices):
keys are pooled after RoPE; the pooling weights carry no -|k|^2/2 term
(the paper's importance weights do); the pooling logits are scaled by s.
RoPE pairs dimension d with d + head_dim/2.

Weights are the system's own tree (`deepspeed_tpu.models.EvaByte.init`'s
layout), upcast one matrix at a time inside the jitted pieces, and the
sequence is walked a window at a time (the projections, the output
projection and the FFN by a window's rows; attention by one window of
queries and one group of heads): at the timed sizes the reference runs
beside the engine's weights and pool, and one layer in float32 is
810 MB, `[16384, 11008]` float32 0.72 GB."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_GROUP = 8     # heads of one attention pass


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + _f32(g))


def _rope(x, pos, theta):
    """x [B, T, H, Dh], pos [T]: rotate pairs (d, d + Dh/2)."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("heads", "eps", "theta", "rotate"))
def _project(x, pos, ln, w, *, heads, eps, theta, rotate):
    """x [B, T, D] float32 rows at positions `pos` -> [B, T, H, Dh]: the
    normed rows times one of wq, wk, wv, rotated for q and k."""
    with jax.default_matmul_precision(HIGHEST):
        B, T, D = x.shape
        y = (_rms(x, ln, eps) @ _f32(w)).reshape(B, T, heads, D // heads)
        return _rope(y, pos, theta) if rotate else y


@functools.partial(jax.jit, static_argnames=("chunk",))
def _summaries(k, v, mu, phi, *, chunk):
    """k, v [B, S, H, Dh] -> k~, v~ [B, S // chunk, H, Dh]."""
    with jax.default_matmul_precision(HIGHEST):
        B, S, H, Dh = k.shape
        n = S // chunk
        kc = k[:, :n * chunk].reshape(B, n, chunk, H, Dh)
        vc = v[:, :n * chunk].reshape(B, n, chunk, H, Dh)
        s = Dh ** -0.5
        wk = jax.nn.softmax(
            s * jnp.einsum("bnchd,hd->bnch", kc, _f32(mu)), axis=2)
        wv = jax.nn.softmax(
            s * jnp.einsum("bnchd,hd->bnch", kc, _f32(phi)), axis=2)
        return (jnp.einsum("bnch,bnchd->bnhd", wk, kc),
                jnp.einsum("bnch,bnchd->bnhd", wv, vc))


@jax.jit
def _attend_window(q, k, v, ks, vs, n_remote):
    """One window's queries q [B, T, h, Dh] over the window's own keys
    k, v [B, T, h, Dh] (causal) and the first `n_remote` summaries
    ks, vs [B, N, h, Dh], one softmax -> [B, T, h, Dh]."""
    with jax.default_matmul_precision(HIGHEST):
        T, N, s = q.shape[1], ks.shape[1], q.shape[-1] ** -0.5
        local = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s
        local = jnp.where(jnp.tril(jnp.ones((T, T), bool)), local, -jnp.inf)
        remote = jnp.einsum("bqhd,bnhd->bhqn", q, ks) * s
        remote = jnp.where(jnp.arange(N) < n_remote, remote, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([local, remote], axis=-1), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p[..., :T], v) + \
            jnp.einsum("bhqn,bnhd->bqhd", p[..., T:], vs)


@jax.jit
def _add_proj(x, a, w):
    with jax.default_matmul_precision(HIGHEST):
        return x + a.reshape(x.shape) @ _f32(w)


@functools.partial(jax.jit, static_argnames=("eps",))
def _add_ffn(x, ln, wg, wu, wd, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        h = _rms(x, ln, eps)
        return x + (jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))) @ _f32(wd)


@functools.partial(jax.jit, static_argnames=("eps", "vocab"))
def _head(x, ln, w, *, eps, vocab):
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x, ln, eps) @ _f32(w[:, :vocab])


def _window_context(q, k, v, ks, vs, at, chunk):
    """The queries of the window that starts at position `at`,
    q [B, T, H, Dh], over that window's keys and the summaries of the
    chunks before it; one group of heads at a time."""
    rows = slice(at, at + q.shape[1])
    return jnp.concatenate([
        _attend_window(q[:, :, g:g + HEAD_GROUP],
                       k[:, rows, g:g + HEAD_GROUP],
                       v[:, rows, g:g + HEAD_GROUP],
                       ks[:, :, g:g + HEAD_GROUP],
                       vs[:, :, g:g + HEAD_GROUP], at // chunk)
        for g in range(0, q.shape[2], HEAD_GROUP)], axis=2)


def _all_summaries(k, v, mu, phi, chunk):
    ks, vs = _summaries(k, v, mu, phi, chunk=chunk)
    if ks.shape[1] == 0:  # shorter than a chunk: nothing is ever remote
        ks = vs = jnp.zeros_like(k[:, :1])
    return ks, vs


def attention(q, k, v, mu, phi, *, window: int, chunk: int):
    """EVA over a whole sequence: q, k, v [B, S, H, Dh] float32 (after
    RoPE) -> [B, S, H, Dh]."""
    ks, vs = _all_summaries(k, v, mu, phi, chunk)
    return jnp.concatenate([
        _window_context(q[:, at:at + window], k, v, ks, vs, at, chunk)
        for at in range(0, q.shape[1], window)], axis=1)


def logits(params, tokens, *, heads: int, eps: float, theta: float,
           window: int, chunk: int, vocab: int):
    """tokens [B, S] int32 -> logits of output head 0, [B, S, vocab]
    float32.  The residual stream is kept as one array a window: a
    layer first takes every window's keys and values, then renews the
    windows one at a time (queries, attention, output projection, FFN),
    so no second whole-sequence array lives beside k and v."""
    S = tokens.shape[1]
    starts = range(0, S, window)
    xs = [_f32(params["wte"][tokens[:, at:at + window]]) for at in starts]
    pos = [at + jnp.arange(x.shape[1]) for at, x in zip(starts, xs)]
    for p in params["blocks"]:
        a, m = p["attn"], p["mlp"]
        proj = functools.partial(_project, ln=p["ln1"]["scale"], heads=heads,
                                 eps=eps, theta=theta)
        k = jnp.concatenate([proj(x, t, w=a["k"], rotate=True)
                             for x, t in zip(xs, pos)], axis=1)
        v = jnp.concatenate([proj(x, t, w=a["v"], rotate=False)
                             for x, t in zip(xs, pos)], axis=1)
        ks, vs = _all_summaries(k, v, a["mu"], a["phi"], chunk)
        for i, at in enumerate(starts):
            q = proj(xs[i], pos[i], w=a["q"], rotate=True)
            x = _add_proj(xs[i], _window_context(q, k, v, ks, vs, at, chunk),
                          a["o"])
            xs[i] = _add_ffn(x, p["ln2"]["scale"], m["gate"], m["up"],
                             m["down"], eps=eps)
        del k, v, ks, vs
    return jnp.concatenate([
        _head(x, params["ln_f"]["scale"], params["lm_head"], eps=eps,
              vocab=vocab) for x in xs], axis=1)


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` takes, from a configuration file."""
    return {"heads": config["num_attention_heads"],
            "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]),
            "window": config["window_size"], "chunk": config["chunk_size"],
            "vocab": config["vocab_size"]}
