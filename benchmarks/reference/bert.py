"""Plain reference for the BERT family: encoder, MLM and NSP heads and the
pre-training loss in straightforward `jax.numpy`, float32, matmuls at
`highest` precision, dropout off, no kernel, no chunked loss.

It follows BERT as the reference's pre-LN modelling file has it
(tests/unit/modelingpreln.py): token + position + type embeddings and a
LayerNorm; blocks that normalise before attention and before the MLP; a
final LayerNorm; the MLM head (dense, GELU, LayerNorm, decoder tied to the
word table plus a bias) and the NSP head on the tanh-pooled first position.
Departure, shared with the system and listed in the configuration's
`assumed`: GELU in its tanh form.  `pre_layer_norm=False` gives the
published post-LN order.

Weights are the system's own tree (`deepspeed_tpu.models.Bert.init`'s
layout), upcast one layer at a time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps", "pre_ln"))
def layer(x, p, *, heads: int, eps: float, pre_ln: bool):
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        B, S, D = x.shape
        h = _ln(x, p["attn_nw"], p["attn_nb"], eps) if pre_ln else x
        qkv = h @ p["attn_qkvw"] + p["attn_qkvb"]
        q, k, v = (t.reshape(B, S, heads, D // heads)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(D // heads)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                         v).reshape(B, S, D)
        a = x + ctx @ p["attn_ow"] + p["attn_ob"]
        if not pre_ln:
            a = _ln(a, p["attn_nw"], p["attn_nb"], eps)
        h = _ln(a, p["norm_w"], p["norm_b"], eps) if pre_ln else a
        h = _gelu_tanh(h @ p["inter_w"] + p["inter_b"])
        out = a + h @ p["output_w"] + p["output_b"]
        if not pre_ln:
            out = _ln(out, p["norm_w"], p["norm_b"], eps)
        return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _embed(emb, input_ids, token_type_ids, *, eps):
    emb = _f32(emb)
    x = emb["word"][input_ids] + emb["position"][:input_ids.shape[1]][None] \
        + emb["token_type"][token_type_ids]
    return _ln(x, emb["ln_w"], emb["ln_b"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "pre_ln"))
def _heads_loss(x, params, mlm_labels, nsp_labels, *, eps, pre_ln):
    with jax.default_matmul_precision(HIGHEST):
        p = _f32({k: v for k, v in params.items() if k != "layers"})
        if pre_ln:
            x = _ln(x, p["final_ln_w"], p["final_ln_b"], eps)
        mh = p["mlm_head"]
        h = _ln(_gelu_tanh(x @ mh["w"] + mh["b"]), mh["ln_w"], mh["ln_b"],
                eps)
        logits = h @ p["embeddings"]["word"].T + mh["decoder_b"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        mask = mlm_labels != -100
        ll = jnp.take_along_axis(
            logp, jnp.where(mask, mlm_labels, 0)[..., None], axis=-1)[..., 0]
        mlm = -jnp.sum(jnp.where(mask, ll, 0.0)) / jnp.maximum(mask.sum(), 1)
        pooled = jnp.tanh(x[:, 0] @ p["pooler"]["w"] + p["pooler"]["b"])
        nsp_logp = jax.nn.log_softmax(
            pooled @ p["nsp_head"]["w"] + p["nsp_head"]["b"], axis=-1)
        nsp = -jnp.mean(jnp.take_along_axis(nsp_logp, nsp_labels[:, None],
                                            axis=-1))
        return mlm + nsp


def stages(params, batch, *, heads: int, eps: float, pre_ln: bool):
    """The pre-training loss of one batch dict as a chain: `embed(rest) ->
    x`, `block(x, p) -> x` for p in blocks, `head(rest, x) -> loss`; `rest`
    is every parameter outside the layers.  Dropout off."""
    ids, types, mlm, nsp = (jnp.asarray(batch[k]) for k in (
        "input_ids", "token_type_ids", "mlm_labels", "nsp_labels"))
    rest = {k: v for k, v in params.items() if k != "layers"}

    def embed(r):
        return _embed(r["embeddings"], ids, types, eps=eps)

    def head(r, x):
        return _heads_loss(x, r, mlm, nsp, eps=eps, pre_ln=pre_ln)

    return (rest, list(params["layers"]), embed,
            functools.partial(layer, heads=heads, eps=eps, pre_ln=pre_ln),
            head)


def loss(params, batch, *, heads: int, eps: float, pre_ln: bool):
    """MLM + NSP pre-training loss of one batch dict, dropout off."""
    rest, blocks, embed, blk, head = stages(params, batch, heads=heads,
                                            eps=eps, pre_ln=pre_ln)
    x = embed(rest)
    for p in blocks:
        x = blk(x, p)
    return head(rest, x)


def for_config(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "eps": config["layer_norm_eps"],
            "pre_ln": config["assumed"]["pre_layer_norm"]}
