"""Plain reference for the GPT-2 family: the forward pass and the loss in
straightforward `jax.numpy`, float32, matmuls at `highest` precision, no
kernel, no cache, no chunking.  It follows the GPT-2 description: learned
positions, pre-LayerNorm blocks, causal softmax attention, tanh-GELU MLP,
final LayerNorm, head tied to the token table.  Departure: none.

Weights are the system's own tree (`deepspeed_tpu.models.GPT.init`'s
layout), upcast one block at a time, so the reference never holds a second
fp32 copy of the model."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def block(x, p, *, heads: int, eps: float):
    """x [B, S, D] float32 -> [B, S, D]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        B, S, D = x.shape
        h = _ln(x, p["ln1"], eps)
        qkv = h @ p["attn"]["qkv"]["w"] + p["attn"]["qkv"]["b"]
        q, k, v = (t.reshape(B, S, heads, D // heads)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(D // heads)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                         v).reshape(B, S, D)
        x = x + ctx @ p["attn"]["proj"]["w"] + p["attn"]["proj"]["b"]
        h = _ln(x, p["ln2"], eps)
        h = _gelu_tanh(h @ p["mlp"]["fc1"]["w"] + p["mlp"]["fc1"]["b"])
        return x + h @ p["mlp"]["fc2"]["w"] + p["mlp"]["fc2"]["b"]


@jax.jit
def _embed(wte, wpe, tokens):
    return wte[tokens].astype(jnp.float32) + \
        wpe[:tokens.shape[1]].astype(jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, wte, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return _ln(x, _f32(ln_f), eps) @ wte.astype(jnp.float32).T


def logits(params, tokens, *, heads: int, eps: float):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    x = _embed(params["wte"], params["wpe"], tokens)
    for p in params["blocks"]:
        x = block(x, p, heads=heads, eps=eps)
    head = params["wte"] if "lm_head" not in params else params["lm_head"].T
    return _head(x, params["ln_f"], head, eps=eps)


@jax.jit
def _mean_ce(lg, labels):
    logp = jax.nn.log_softmax(lg, axis=-1)
    valid = labels >= 0
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, ll, 0.0)) / jnp.maximum(valid.sum(), 1)


def stages(params, batch, *, heads: int, eps: float):
    """The loss of batch = (tokens, labels) as a chain: `embed(rest) -> x`,
    `block(x, p) -> x` for p in blocks, `head(rest, x) -> loss`; `rest` is
    every parameter outside the blocks."""
    tokens, labels = (jnp.asarray(a) for a in batch)
    rest = {k: v for k, v in params.items() if k != "blocks"}

    def embed(r):
        return _embed(r["wte"], r["wpe"], tokens)

    def head(r, x):
        w = r["wte"] if "lm_head" not in r else r["lm_head"].T
        return _mean_ce(_head(x, r["ln_f"], w, eps=eps), labels)

    return (rest, list(params["blocks"]), embed,
            functools.partial(block, heads=heads, eps=eps), head)


def loss(params, batch, *, heads: int, eps: float):
    """Mean next-token cross entropy of batch = (tokens, labels)."""
    rest, blocks, embed, blk, head = stages(params, batch, heads=heads,
                                            eps=eps)
    x = embed(rest)
    for p in blocks:
        x = blk(x, p)
    return head(rest, x)


def for_config(config: dict) -> dict:
    """The keyword arguments `logits` and `loss` take, from a config file."""
    return {"heads": config["n_head"], "eps": config["layer_norm_epsilon"]}
