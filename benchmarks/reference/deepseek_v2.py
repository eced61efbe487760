"""Plain reference for DeepSeek-V2(-Lite): the forward pass in
straightforward `jax.numpy`, float32, matmuls at `highest` precision, no
kernel, no cache, no absorbed products, no grouping of tokens by expert.
It follows the published `modeling_deepseek.py` (`model_type`
`deepseek_v2`), for x [T, D] in a layer:

  h = RMSNorm(x; w, eps)                  (gain w, no unit offset)
  q = h Wq -> H heads of [q_nope (dn) | q_rope (dr)]
  [c | k_r] = h Wkv_a -> r + dr;  c <- RMSNorm(c; w_kv, eps)
  k_r <- RoPE(k_r) (one rotary key for all heads), q_rope <- RoPE(q_rope)
  [k_nope | v] = c Wkv_b -> H x (dn + dv)             (the expanded path)
  score = (q_nope.k_nope + q_rope.k_r) s,  s = (dn + dr)^-1/2 m(mscale_all_dim)^2
  causal softmax, o = sum p v -> [T, H dv] Wo;  x <- x + o
  h2 = RMSNorm(x).  The first `first_k_dense_replace` layers:
  down(silu(gate h2) * up h2).  The others: g = softmax(h2 Wg) over the E
  routed experts in float32, the `num_experts_per_tok` largest kept as
  they are (no renormalisation, `routed_scaling_factor` 1), y = sum_i g_i
  E_i(h2) + S(h2), S the shared experts as one gated FFN.  x <- x + y.
  Final RMSNorm, untied head.

RoPE over the dr rotary dims with YaRN's frequencies (inverse
frequencies blended between theta^(-2i/dr) and that / factor by the
linear ramp between the two correction dims of `beta_fast`, `beta_slow`
over `original_max_position_embeddings`), cos and sin times
m(mscale) / m(mscale_all_dim), m(s) = 0.1 s ln(factor) + 1.

Departures (listed under `assumed` in the configuration file; the system
makes the same choices): RoPE pairs dimension d with d + dr/2 (what the
published code reaches after its de-interleave).  Every expert is
computed for every token and weighted by g where selected, 0 elsewhere.

Weights are the system's own tree (`deepspeed_tpu.models.DeepSeekV2.init`'s
layout), upcast a piece at a time inside the jitted pieces: attention a
head at a time, the experts one at a time (`lax.map`), the head a block
of the vocabulary at a time — at the timed sizes the reference runs
beside the engine's weights and pool, and the head alone is 839 MB in
float32."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"
HEAD_BLOCK = 8192      # most vocabulary columns of one head product
NEG_INF = -1e30


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` needs, from a configuration file."""
    return {"heads": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"],
            "rank": config["kv_lora_rank"],
            "top_k": config["num_experts_per_tok"],
            "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]),
            "yarn": tuple(sorted((k, v) for k, v in
                                 (config["rope_scaling"] or {}).items()
                                 if k != "type"))}


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def mscale(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: dict):
    """The dim/2 inverse frequencies: plain where `yarn` is empty."""
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not yarn:
        return extra

    def correction_dim(rotations):
        return dim * math.log(yarn["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / yarn["factor"] * ramp + extra * (1.0 - ramp)


def _rope(x, pos, theta, yarn):
    """x [..., T, dr] at positions pos [T]: rotate pairs (d, d + dr/2)."""
    dr = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(dr, theta, yarn)
    m = (mscale(yarn["factor"], yarn["mscale"])
         / mscale(yarn["factor"], yarn["mscale_all_dim"])) if yarn else 1.0
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    a, b = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "v_dim", "rank", "eps", "theta", "yarn"))
def _attention(x, p, ln, *, heads, nope, rope, v_dim, rank, eps, theta,
               yarn):
    """x [B, S, D] float32 -> x + attention, the expanded path."""
    with jax.default_matmul_precision(HIGHEST):
        yarn = dict(yarn)
        B, S, D = x.shape
        pos = jnp.arange(S)
        h = _rms(x, ln, eps)
        q = (h @ _f32(p["q"])).reshape(B, S, heads, nope + rope)
        ckr = h @ _f32(p["kv_a"])
        c = _rms(ckr[..., :rank], p["kv_norm"]["scale"], eps)
        k_r = _rope(ckr[..., rank:], pos, theta, yarn)          # [B, S, dr]
        kv = (c @ _f32(p["kv_b"])).reshape(B, S, heads, nope + v_dim)
        scale = (nope + rope) ** -0.5
        if yarn:
            scale *= mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
        causal = pos[None, :] <= pos[:, None]

        def head(args):
            qh, kvh = args                  # [B, S, dn + dr], [B, S, dn + dv]
            q_r = _rope(qh[..., nope:], pos, theta, yarn)
            s = (jnp.einsum("bqd,bkd->bqk", qh[..., :nope], kvh[..., :nope])
                 + jnp.einsum("bqd,bkd->bqk", q_r, k_r)) * scale
            pr = jax.nn.softmax(jnp.where(causal[None], s, NEG_INF), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", pr, kvh[..., nope:])

        o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kv, 2, 0)))
        o = jnp.moveaxis(o, 0, 2).reshape(B, S, heads * v_dim)
        return x + o @ _f32(p["o"])


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, p, ln, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return x + _gated(_rms(x, ln, eps), p["gate"], p["up"], p["down"])


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _expert_ffn(x, p, ln, *, top_k, eps):
    """Every expert for every token, weighted by its gate where it is
    among the token's `top_k`, by 0 elsewhere; the shared experts once."""
    with jax.default_matmul_precision(HIGHEST):
        h = _rms(x, ln, eps)
        g = jax.nn.softmax(h @ _f32(p["router"]), axis=-1)       # [B, S, E]
        kth = jax.lax.top_k(g, top_k)[0][..., -1:]
        # greedy top-k keeps the first of equal scores; scores of seeded
        # float32 weights do not tie exactly
        w = jnp.where(g >= kth, g, 0.0)
        e = p["experts"]

        def one(acc, args):
            gate, up, down, we = args
            return acc + we[..., None] * _gated(h, gate, up, down), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (e["gate"], e["up"], e["down"],
                             jnp.moveaxis(w, -1, 0)))
        s = p["shared"]
        return x + y + _gated(h, s["gate"], s["up"], s["down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln, w, *, eps):
    """Final norm and the untied head, a block of the vocabulary at a
    time, written into the one [B, S, V] array."""
    with jax.default_matmul_precision(HIGHEST):
        h = _rms(x, ln, eps)
        V = w.shape[1]
        n = next(n for n in range(1, V + 1)
                 if V % n == 0 and V // n <= HEAD_BLOCK)
        blk = V // n

        def one(i, out):
            cols = jax.lax.dynamic_slice_in_dim(w, i * blk, blk, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, h @ _f32(cols), i * blk, axis=2)

        return jax.lax.fori_loop(
            0, n, one, jnp.zeros(x.shape[:2] + (V,), jnp.float32))


def logits(params, tokens, *, heads, nope, rope, v_dim, rank, top_k, eps,
           theta, yarn):
    """tokens [B, S] int32 -> [B, S, V] float32."""
    x = _f32(params["wte"][tokens])
    for p in params["blocks"]:
        x = _attention(x, p["attn"], p["ln1"]["scale"], heads=heads,
                       nope=nope, rope=rope, v_dim=v_dim, rank=rank, eps=eps,
                       theta=theta, yarn=yarn)
        if "router" in p["mlp"]:
            x = _expert_ffn(x, p["mlp"], p["ln2"]["scale"], top_k=top_k,
                            eps=eps)
        else:
            x = _dense_ffn(x, p["mlp"], p["ln2"]["scale"], eps=eps)
    return _head(x, params["ln_f"]["scale"], params["lm_head"], eps=eps)
