"""Autoregressive generation for the GPT family with a static KV cache.

The reference ships no generation loop (its inference engine arrived in
later versions); this is the TPU-native one: a prefill pass caches K/V per
block, then a `lax.scan` decodes one token per step against fixed-shape
caches (dynamic_update_slice writes, position-masked attention) — fully
jittable, no dynamic shapes, MXU-friendly single-token matmuls batched
over B.

Greedy decoding parity against HuggingFace's generate() is pinned in
tests/test_generation.py via the models/hf.py weight import.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .gpt import GPT, layer_norm

NEG_INF = -1e30


def kth_largest(x, k):
    """The k-th largest value of every row of float32 `x` [N, V], `k`
    [N] in 1..V, exactly and without a sort: float32 bit patterns, the
    magnitude bits flipped under a set sign, order as int32 the way the
    floats do (-inf lowest, -0.0 under +0.0 — `jnp.sort`'s total
    order), so the answer is the largest t with `count(row >= t) >= k`,
    built from the sign bit down: 32 compare-and-count passes over
    [N, V] whatever k is."""
    def image(bits):                 # its own inverse
        return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)

    keys = image(jax.lax.bitcast_convert_type(x, jnp.int32))

    def reaches(t):
        return jnp.sum(keys >= t[:, None], axis=-1, dtype=jnp.int32) >= k

    zero = jnp.zeros(k.shape, jnp.int32)
    t = jnp.where(reaches(zero), zero, jnp.iinfo(jnp.int32).min)

    def lower_bit(i, t):
        up = t | (jnp.int32(1 << 30) >> i)
        return jnp.where(reaches(up), up, t)

    t = jax.lax.fori_loop(0, 31, lower_bit, t)
    return jax.lax.bitcast_convert_type(image(t), jnp.float32)


def _split_qkv(h, qkv_p, B, T, H, Dh):
    qkv = h @ qkv_p["w"].astype(h.dtype) + qkv_p["b"].astype(h.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = lambda t: t.reshape(B, T, H, Dh)
    return shape(q), shape(k), shape(v)


def _block_with_cache(p, cfg, x, ck, cv, pos):
    """One decoder block over x [B, T, D]; returns output + updated
    caches. `pos` = index of x's first token in the sequence; attention
    sees cache positions <= pos + t (causal)."""
    B, T, D = x.shape
    H, Dh = cfg.num_heads, cfg.head_dim
    L = ck.shape[1]
    h = layer_norm(x, p["ln1"], cfg.layer_norm_eps)
    q, k, v = _split_qkv(h, p["attn"]["qkv"], B, T, H, Dh)
    # cast to the cache dtype on write (identity when they agree): a
    # bf16 cache under fp32 params stores rounded K/V, mirroring the
    # serving engine's kv_dtype="bf16" dense store bit-for-bit
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                      (0, pos, 0, 0))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (Dh ** -0.5)
    q_idx = pos + jnp.arange(T)[:, None]
    k_idx = jnp.arange(L)[None, :]
    scores = jnp.where(q_idx >= k_idx, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cv.dtype), cv)
    attn = attn.reshape(B, T, D)
    attn = attn @ p["attn"]["proj"]["w"].astype(h.dtype) + \
        p["attn"]["proj"]["b"].astype(h.dtype)
    x = x + attn
    h = layer_norm(x, p["ln2"], cfg.layer_norm_eps)
    h = h @ p["mlp"]["fc1"]["w"].astype(h.dtype) + \
        p["mlp"]["fc1"]["b"].astype(h.dtype)
    h = jax.nn.gelu(h, approximate=True)
    h = h @ p["mlp"]["fc2"]["w"].astype(h.dtype) + \
        p["mlp"]["fc2"]["b"].astype(h.dtype)
    return x + h, ck, cv


def _forward_cached(model: GPT, params, tokens, caches, pos):
    """tokens [B, T] at absolute position `pos` -> (last-token logits,
    updated caches)."""
    cfg = model.config
    B, T = tokens.shape
    x = params["wte"][tokens] + \
        jax.lax.dynamic_slice_in_dim(params["wpe"], pos, T, axis=0)[None]
    new_caches = []
    for bp, (ck, cv) in zip(params["blocks"], caches):
        x, ck, cv = _block_with_cache(bp, cfg, x, ck, cv, pos)
        new_caches.append((ck, cv))
    x = layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
    w = (params["wte"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x[:, -1, :] @ w.astype(x.dtype)
    return logits.astype(jnp.float32), new_caches


def _init_caches(model: GPT, B, L, dtype):
    cfg = model.config
    z = lambda: jnp.zeros((B, L, cfg.num_heads, cfg.head_dim), dtype)
    return [(z(), z()) for _ in range(cfg.num_layers)]


@partial(jax.jit, static_argnums=(0, 3, 5, 6, 7, 8, 9))
def _generate_jit(model, params, prompt, max_new_tokens, rng, temperature,
                  cache_len, top_k, top_p, cache_dtype=None):
    B, T = prompt.shape
    caches = _init_caches(
        model, B, cache_len,
        params["wte"].dtype if cache_dtype is None else cache_dtype)
    logits, caches = _forward_cached(model, params, prompt, caches, 0)

    flat, treedef = jax.tree_util.tree_flatten(caches)

    def sample(logits, rng):
        greedy = jnp.argmax(logits, axis=-1)
        if temperature == 0.0:
            return greedy
        logits = logits.astype(jnp.float32) / temperature
        V = logits.shape[-1]
        if top_k > 0 or top_p < 1.0:
            # ONE descending sort serves both filters (HF semantics:
            # k-truncate first, then nucleus over the renormalized
            # survivors — masking the sorted tail reproduces the sort of
            # the masked logits exactly)
            sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
            if top_k > 0:
                k = min(top_k, V)  # clamp like HF for generous defaults
                kth = sorted_desc[..., k - 1][..., None]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
                # mask the sorted copy by VALUE, not position: ties at the
                # k-th logit survive the live mask above (HF semantics),
                # so they must stay in the nucleus computation too
                sorted_desc = jnp.where(sorted_desc < kth, -jnp.inf,
                                        sorted_desc)
            if top_p < 1.0:
                # nucleus: keep the smallest set with cum prob > top_p.
                # Boundary semantics match modern HF TopPLogitsWarper, which
                # removes (ascending sort) where cumsum <= 1-top_p — i.e.
                # keep while the PREVIOUS descending cumulative is strictly
                # < top_p. Exact-boundary ties drop the marginal token.
                probs = jax.nn.softmax(sorted_desc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = jnp.sum(cum - probs < top_p, axis=-1, keepdims=True)
                cutoff = jnp.take_along_axis(sorted_desc, keep - 1, axis=-1)
                logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(rng, logits, axis=-1)

    def step(carry, _):
        logits, flat_caches, pos, rng = carry
        rng, sub = jax.random.split(rng)
        tok = sample(logits, sub)
        caches = jax.tree_util.tree_unflatten(treedef, flat_caches)
        logits, caches = _forward_cached(
            model, params, tok[:, None], caches, pos)
        flat_caches = jax.tree_util.tree_leaves(caches)
        return (logits, flat_caches, pos + 1, rng), tok

    (_, _, _, _), toks = jax.lax.scan(
        step, (logits, flat, jnp.asarray(T), rng),
        None, length=max_new_tokens)
    return toks.T  # [B, max_new_tokens]


def generate(model: GPT, params, prompt, max_new_tokens: int,
             temperature: float = 0.0, rng: Optional[jax.Array] = None,
             cache_len: Optional[int] = None, top_k: int = 0,
             top_p: float = 1.0, cache_dtype=None):
    """Generate continuations. prompt [B, T] int32; returns
    [B, max_new_tokens]. temperature 0 = greedy; otherwise categorical
    sampling with `rng`, optionally truncated to the top_k highest
    logits and/or the top_p nucleus (HF-style semantics: k first, then
    p). The model's dropout must be 0 (inference).  `cache_dtype`
    overrides the KV cache's storage dtype (default: the param dtype);
    a bf16 cache under fp32 params is the oracle for the serving
    engine's kv_dtype="bf16" parity pin."""
    cfg = model.config
    if cfg.num_experts > 1 or cfg.pipeline_stages > 1:
        raise NotImplementedError(
            "generate() supports plain dense GPT configs (no MoE layers, "
            "no pipeline-stacked blocks)")
    B, T = prompt.shape
    L = cache_len or min(cfg.max_seq_len, T + max_new_tokens)
    if T + max_new_tokens > cfg.max_seq_len:
        raise ValueError(f"prompt {T} + new {max_new_tokens} exceeds "
                         f"max_seq_len {cfg.max_seq_len}")
    if T + max_new_tokens > L:
        # an undersized cache would CLAMP dynamic_update_slice writes and
        # silently corrupt late tokens
        raise ValueError(f"cache_len {L} < prompt {T} + new "
                         f"{max_new_tokens}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if top_k < 0 or not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_k must be >= 0 and 0 < top_p <= 1, got "
                         f"{top_k}, {top_p}")
    if cache_dtype is not None:
        # canonicalize to a hashable np.dtype for the static argnum
        cache_dtype = jnp.zeros((), cache_dtype).dtype
    return _generate_jit(model, params, jnp.asarray(prompt),
                         int(max_new_tokens), rng, float(temperature),
                         int(L), int(top_k), float(top_p), cache_dtype)
