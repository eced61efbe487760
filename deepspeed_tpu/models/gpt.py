"""GPT model family — the framework's flagship decoder-only transformer.

The reference ships no model zoo (SURVEY.md §1: "There is no model zoo...");
its model tests drive an external Megatron GPT-2
(/root/reference/tests/model/Megatron_GPT2/). This framework is standalone,
so the GPT family lives in-tree, built TPU-first:

* pure-function params pytree (nested dicts), bf16-friendly, static shapes;
* Megatron-style tensor parallelism expressed as `PartitionSpec`s over the
  `model` mesh axis (column-parallel QKV/fc1, row-parallel proj/fc2,
  vocab-parallel embedding) — XLA inserts the psums the reference delegates
  to Megatron's mpu (reference engine.py:622-641 just *accepts* an mpu);
* sequence sharding of activations over the `seq` axis
  (with_sharding_constraint), ring attention optional via
  deepspeed_tpu.parallel.ring_attention;
* `jax.checkpoint` rematerialisation per block (the analogue of
  activation_checkpointing/checkpointing.py) behind `remat=True`;
* attention dispatches through ops.transformer.attention (Pallas flash
  attention on TPU, fused-XLA fallback elsewhere).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comm.mesh import DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS
from ..ops.transformer.attention import multihead_attention
from ..runtime.module import TrainModule


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 50257 padded to a 128 multiple
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: Optional[int] = None       # default 4*d_model
    dropout: float = 0.0
    embed_dropout: float = 0.0
    attn_dropout: float = -1.0       # attention-probability dropout;
                                     # -1 -> follow `dropout` (reference
                                     # transformer config keeps the two
                                     # ratios separate too)
    layer_norm_eps: float = 1e-5
    tie_embeddings: bool = True
    loss_chunks: int = 0             # CE chunking: 0 auto, 1 off, n chunks
    loss_impl: str = "auto"          # auto/xla: chunked XLA CE; pallas:
                                     # fused streaming kernel (no logits in
                                     # HBM; invalid with vocab-parallel TP)
    remat: bool = False              # per-block rematerialisation
    shard_activations: bool = True   # seq/data sharding constraints
    attn_impl: str = "auto"          # auto|pallas|xla (kernels/registry)
    flash_block_q: int = 0           # ring attention's query tile: bounds
                                     # its score memory (0: untiled); the
                                     # flash kernel tiles itself
    param_dtype: Any = jnp.float32
    pipeline_stages: int = 1         # >1: stack blocks + pipeline over `pipe`
    pipeline_micro_batches: int = 0  # 0 -> default (= pipe size)
    sequence_parallel: bool = False  # SP attention over the `seq` axis
    sequence_parallel_impl: str = "ring"  # ring | ring_zigzag | ulysses
    # Mixture-of-Experts (beyond-parity; reference has no MoE, SURVEY §2.2)
    num_experts: int = 1             # >1: MoE FFN every moe_layer_freq layers
    moe_top_k: int = 1
    moe_layer_freq: int = 2          # MoE on layers with idx % freq == 1
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 1e-2

    def __post_init__(self):
        if self.d_ff is None:
            self.d_ff = 4 * self.d_model
        assert self.d_model % self.num_heads == 0
        if self.num_experts > 1 and self.pipeline_stages > 1:
            raise ValueError("MoE and pipeline mode are mutually exclusive "
                             "for now (stacked stage params must be uniform)")

    def is_moe_layer(self, idx: int) -> bool:
        # freq f -> layers f-1, 2f-1, ... (f=1: every layer; f=2: odd layers)
        return (self.num_experts > 1 and
                idx % self.moe_layer_freq == self.moe_layer_freq - 1)

    def moe_config(self):
        from ..moe.layer import MoEConfig

        return MoEConfig(d_model=self.d_model, d_ff=self.d_ff,
                         num_experts=self.num_experts, top_k=self.moe_top_k,
                         capacity_factor=self.moe_capacity_factor)

    @property
    def head_dim(self):
        return self.d_model // self.num_heads


# Standard GPT-2 sizes; "xl" is the 1.5B north-star model (BASELINE.md).
GPT2_SIZES: Dict[str, Dict[str, int]] = {
    "nano":   dict(num_layers=3,  num_heads=3,  d_model=48,  max_seq_len=128,
                   vocab_size=256),
    "small":  dict(num_layers=12, num_heads=12, d_model=768),
    "medium": dict(num_layers=24, num_heads=16, d_model=1024),
    "large":  dict(num_layers=36, num_heads=20, d_model=1280),
    "xl":     dict(num_layers=48, num_heads=25, d_model=1600),
}


def gpt2_config(size: str = "small", **overrides) -> GPTConfig:
    base = dict(GPT2_SIZES[size])
    base.update(overrides)
    return GPTConfig(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_wte(rng, cfg: GPTConfig):
    """Token-embedding table — THE single definition of its init scale;
    GPT.init, the streaming init, and the LayerSpec pipeline form
    (gpt_pipe.py) all share it so their initializations cannot drift."""
    return (jax.random.normal(rng, (cfg.vocab_size, cfg.d_model))
            * 0.02).astype(cfg.param_dtype)


def init_wpe(rng, cfg: GPTConfig):
    return (jax.random.normal(rng, (cfg.max_seq_len, cfg.d_model))
            * 0.01).astype(cfg.param_dtype)


def init_final_ln(cfg: GPTConfig):
    return {"scale": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "bias": jnp.zeros((cfg.d_model,), cfg.param_dtype)}


def init_lm_head(rng, cfg: GPTConfig):
    return (jax.random.normal(rng, (cfg.d_model, cfg.vocab_size))
            * 0.02).astype(cfg.param_dtype)


def _init_block(rng, cfg: GPTConfig, layer_idx: int = 0):
    k = jax.random.split(rng, 5)
    d, f = cfg.d_model, cfg.d_ff
    std = 0.02
    proj_std = std / math.sqrt(2 * cfg.num_layers)  # GPT-2 residual scaling
    dt = cfg.param_dtype
    return {
        "ln1": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
        "attn": {
            "qkv": {"w": (jax.random.normal(k[0], (d, 3 * d)) * std).astype(dt),
                    "b": jnp.zeros((3 * d,), dt)},
            "proj": {"w": (jax.random.normal(k[1], (d, d)) * proj_std).astype(dt),
                     "b": jnp.zeros((d,), dt)},
        },
        "ln2": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
    } | (
        {"moe": _moe(cfg).init(k[4], param_dtype=dt)}
        if cfg.is_moe_layer(layer_idx) else
        {"mlp": {
            "fc1": {"w": (jax.random.normal(k[2], (d, f)) * std).astype(dt),
                    "b": jnp.zeros((f,), dt)},
            "fc2": {"w": (jax.random.normal(k[3], (f, d)) * proj_std).astype(dt),
                    "b": jnp.zeros((d,), dt)},
        }})


def _moe(cfg: GPTConfig):
    from ..moe.layer import MoE

    return MoE(cfg.moe_config())


def _block_specs(cfg: GPTConfig, layer_idx: int = 0):
    """Megatron TP layout: column-parallel qkv/fc1 (shard output dim over
    `model`), row-parallel proj/fc2 (shard input dim). MoE layers swap the
    MLP specs for expert-parallel ones (expert dim over `data`)."""
    if cfg.is_moe_layer(layer_idx):
        from ..moe.layer import MoE

        return {
            "ln1": {"scale": P(), "bias": P()},
            "attn": {
                "qkv": {"w": P(None, MODEL_AXIS), "b": P(MODEL_AXIS)},
                "proj": {"w": P(MODEL_AXIS, None), "b": P()},
            },
            "ln2": {"scale": P(), "bias": P()},
            "moe": MoE.param_specs(),
        }
    return {
        "ln1": {"scale": P(), "bias": P()},
        "attn": {
            "qkv": {"w": P(None, MODEL_AXIS), "b": P(MODEL_AXIS)},
            "proj": {"w": P(MODEL_AXIS, None), "b": P()},
        },
        "ln2": {"scale": P(), "bias": P()},
        "mlp": {
            "fc1": {"w": P(None, MODEL_AXIS), "b": P(MODEL_AXIS)},
            "fc2": {"w": P(MODEL_AXIS, None), "b": P()},
        },
    }


# ---------------------------------------------------------------------------
# forward pieces (pure functions)
# ---------------------------------------------------------------------------

def layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) +
            p["bias"].astype(jnp.float32)).astype(x.dtype)


def _dropout(x, rate, rng, train):
    # counter-hash mask, not bernoulli/threefry — see
    # ops/transformer/dropout.py for why
    from ..ops.transformer.dropout import hash_dropout

    return hash_dropout(x, rate, rng, train)


def _constrain(x, cfg: GPTConfig, spec):
    if not cfg.shard_activations:
        return x
    from ..comm.mesh import peek_mesh

    info = peek_mesh()
    if info is not None and info.hierarchical:
        # the literal "data" axis does not exist on a hierarchical mesh
        # (comm.hierarchy factors it into data_outer/data_inner): expand
        # it so the constraint binds instead of being swallowed below
        spec = P(*[info.data_spec if s == DATA_AXIS else s for s in spec])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # no mesh in scope (e.g. plain jit in unit tests)
        return x


def gpt_block(x, p, cfg: GPTConfig, rng=None, train=True):
    """One pre-LN transformer block. x: [B, S, D]."""
    B, S, D = x.shape
    H = cfg.num_heads
    r1 = r2 = r3 = None
    if rng is not None:
        r1, r2, r3 = jax.random.split(rng, 3)

    h = layer_norm(x, p["ln1"], cfg.layer_norm_eps)
    attn_rate = cfg.dropout if cfg.attn_dropout < 0 else cfg.attn_dropout
    qkv = h @ p["attn"]["qkv"]["w"].astype(h.dtype) + \
        p["attn"]["qkv"]["b"].astype(h.dtype)
    q, kk, v = jnp.split(qkv, 3, axis=-1)
    split_heads = lambda t: t.reshape(B, S, H, D // H)
    if cfg.sequence_parallel and cfg.sequence_parallel_impl == "ulysses":
        from ..parallel.ulysses import ulysses_attention

        # every device holds the full sequence for its heads, so
        # probability dropout works exactly as on the dense path
        attn = ulysses_attention(
            split_heads(q), split_heads(kk), split_heads(v),
            multihead_attention, causal=True, impl=cfg.attn_impl,
            dropout_rate=attn_rate, dropout_rng=r1, train=train)
    elif cfg.sequence_parallel:
        if cfg.sequence_parallel_impl not in ("ring", "ring_zigzag"):
            raise ValueError(
                f"unknown sequence_parallel_impl "
                f"{cfg.sequence_parallel_impl!r}; use 'ring', "
                f"'ring_zigzag' or 'ulysses'")
        if train and attn_rate > 0.0 and r1 is not None:
            # the ring formulation has no attention-probability dropout
            # (its block walk keeps probabilities implicit and carries no
            # mask state) — failing is honest, silently skipping is not;
            # ulysses runs dropout in-kernel. rng=None configs (e.g. the
            # SPMD pipeline trunk) treat dropout as inert on every path.
            raise ValueError(
                "attention-probability dropout is not supported on the "
                "ring/ring_zigzag sequence-parallel path; use "
                "sequence_parallel_impl='ulysses', or attn_dropout=0.0 "
                "to keep residual/MLP dropout without it")
        from ..parallel.ring_attention import ring_attention

        # ring_zigzag: the trunk permuted the sequence into the zigzag
        # layout once after the embedding, so every block's attention
        # runs the load-balanced causal ring (~2x fewer FLOPs)
        attn = ring_attention(
            split_heads(q), split_heads(kk), split_heads(v), causal=True,
            layout=("zigzag" if cfg.sequence_parallel_impl == "ring_zigzag"
                    else "contiguous"),
            # bounds per-step score memory at [B, H, block_q, chunk]
            block_q=cfg.flash_block_q)
    else:
        attn = multihead_attention(split_heads(q), split_heads(kk),
                                   split_heads(v), causal=True,
                                   impl=cfg.attn_impl,
                                   dropout_rate=attn_rate,
                                   dropout_rng=r1, train=train)
    attn = attn.reshape(B, S, D)
    attn = attn @ p["attn"]["proj"]["w"].astype(h.dtype) + \
        p["attn"]["proj"]["b"].astype(h.dtype)
    x = x + _dropout(attn, cfg.dropout, r2, train)
    x = _constrain(x, cfg, P(DATA_AXIS, SEQ_AXIS, None))

    h = layer_norm(x, p["ln2"], cfg.layer_norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        r_moe = None
        if r3 is not None:
            r_moe, r3 = jax.random.split(r3)
        h, aux = _moe(cfg)(p["moe"], h, rng=r_moe, train=train)
    else:
        h = h @ p["mlp"]["fc1"]["w"].astype(h.dtype) + \
            p["mlp"]["fc1"]["b"].astype(h.dtype)
        h = jax.nn.gelu(h, approximate=True)
        h = _constrain(h, cfg, P(DATA_AXIS, SEQ_AXIS, MODEL_AXIS))
        h = h @ p["mlp"]["fc2"]["w"].astype(h.dtype) + \
            p["mlp"]["fc2"]["b"].astype(h.dtype)
    x = x + _dropout(h, cfg.dropout, r3, train)
    return _constrain(x, cfg, P(DATA_AXIS, SEQ_AXIS, None)), aux


def _ce_rows(logits32, labels, valid):
    """Sum of masked next-token NLL over rows, from fp32 logits.

    `logsumexp - label_logit` instead of materialising the [N, V] fp32
    log-softmax the previous implementation wrote to HBM — backward is the
    standard softmax-minus-onehot XLA derives from this form."""
    lse = jax.nn.logsumexp(logits32, axis=-1)
    ll = jnp.take_along_axis(logits32, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, lse - ll, 0.0))


def _softmax_xent_from_hidden(x, w, labels, valid, n_chunks=0,
                              impl="auto", bias=None):
    """Fused projection + cross entropy: hidden states [N, D] and the [D, V]
    head weight go straight to summed NLL without a [N, V] activation
    surviving the loss.

    The projection runs with fp32 MXU accumulation (preferred_element_type)
    so no separate bf16-logits buffer + fp32 cast is materialised — the
    single biggest HBM cost of the naive CE at GPT-2 vocab (N·V·4 bytes,
    ~1.6 GB at micro 8 / seq 1024). With n_chunks > 1 the rows are processed
    by a rematerialised lax.scan, so peak memory holds one [N/c, V] chunk;
    backward recomputes each chunk's logits (flash-attention-style,
    applied to the LM head).

    n_chunks: 0 = auto (chunks of ~2048 rows for large-vocab models),
    1 = single fused matmul, n = explicit chunk count (must divide N).
    """
    N, D = x.shape
    V = w.shape[-1]

    if impl == "pallas" and bias is not None:
        from ..utils.logging import logger

        logger.warning("loss_impl='pallas': fused kernel carries no "
                       "decoder bias; using the XLA path")
        impl = "xla"
    if impl == "pallas":
        from ..comm.mesh import peek_mesh
        from ..ops.transformer.fused_xent import (fused_softmax_xent_sum,
                                                  pick_blocks)

        info = peek_mesh()
        if info is not None and info.mesh.shape.get("model", 1) > 1:
            raise ValueError(
                "loss_impl='pallas' is invalid with vocab-parallel TP "
                "(model axis > 1): the kernel's logsumexp is row-global")
        blocks = pick_blocks(N, V)
        if blocks:
            return fused_softmax_xent_sum(x, jnp.asarray(w), labels, valid,
                                          *blocks)
        from ..utils.logging import logger

        logger.warning(f"loss_impl='pallas': shapes N={N}, V={V} have no "
                       f"lane-aligned block divisor; using the XLA path")

    def project(rows):
        out = jax.lax.dot_general(rows, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out

    if n_chunks == 0:  # auto: only chunk when the logits buffer is large
        # enough to matter against TPU HBM (16 GB on v5e) — chunking costs
        # a full logit recompute in backward, so below ~4 GB of fp32
        # logits the single fused matmul wins; GPT-2 at micro 8 / seq 1024
        # (1.6 GB) and the BERT-large seq-128 recipe (1 GB) stay unchunked.
        # Above the threshold, chunk count is sized from the SAME bytes
        # (≈2 GB per chunk) so the decision and the count can't disagree
        # at small N / huge V
        total = N * V * 4
        n_chunks = -(-total // (2 << 30)) if total > 4 << 30 else 1
    # clamp BEFORE the fix-up walk: a requested count above N (e.g.
    # loss_chunks=100 at N=32) has no divisor of N above it, so the
    # upward search below would spin forever at trace time; N itself is
    # always reachable (chunks of one row)
    n_chunks = min(n_chunks, N)
    # fix up to a divisor of N by adding chunks (smaller chunks — never
    # backslide below the byte-derived count, which could silently undo
    # the chunking decision at awkward N)
    while n_chunks > 1 and N % n_chunks:
        n_chunks += 1
    if n_chunks <= 1:
        return _ce_rows(project(x), labels, valid)

    def body(carry, inp):
        rows, lc, vc = inp
        return carry + _ce_rows(project(rows), lc, vc), None

    total, _ = jax.lax.scan(
        jax.checkpoint(body),
        jnp.zeros((), jnp.float32),
        (x.reshape(n_chunks, N // n_chunks, D),
         labels.reshape(n_chunks, -1), valid.reshape(n_chunks, -1)))
    return total


class GPT(TrainModule):
    """Decoder-only LM implementing the engine's TrainModule protocol."""

    def __init__(self, config: GPTConfig):
        self.config = config
        self.param_specs = self._build_specs()

    def layer_spec(self):
        """What the serving engine builds its programs from
        (models/layer_spec.py)."""
        from .layer_spec import LayerSpec

        cfg = self.config
        if cfg.num_experts > 1 or cfg.pipeline_stages > 1:
            raise NotImplementedError(
                "serving takes a dense GPT: `serving/layers.py` has no "
                "expert layer (`num_experts` > 1: dropless routing and "
                "experts in the served block are not built) and takes "
                "`blocks` as a list with one entry a layer, not stacked "
                "for pipeline stages (`pipeline_stages` > 1)")
        return LayerSpec(norm="layernorm", positions="learned",
                         attention="paged", ffn="gelu_mlp",
                         head="tied" if cfg.tie_embeddings else "untied",
                         eps=cfg.layer_norm_eps).validate()

    # -- init ----------------------------------------------------------
    def init(self, rng):
        cfg = self.config
        keys = jax.random.split(rng, cfg.num_layers + 3)
        params = {
            "wte": init_wte(keys[0], cfg),
            "wpe": init_wpe(keys[1], cfg),
            "blocks": self._init_blocks(keys[2:2 + cfg.num_layers], cfg),
            "ln_f": init_final_ln(cfg),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_lm_head(keys[-1], cfg)
        return params

    def _init_blocks(self, keys, cfg):
        blocks = [_init_block(k, cfg, i) for i, k in enumerate(keys)]
        if cfg.pipeline_stages > 1:
            from ..parallel.pipeline import stack_stage_params

            return stack_stage_params(blocks)
        return blocks

    def _build_specs(self):
        cfg = self.config
        if cfg.pipeline_stages > 1:
            # stacked blocks: leading layer dim sharded over `pipe`
            blocks = jax.tree_util.tree_map(
                lambda s: P(PIPE_AXIS, *s), _block_specs(cfg),
                is_leaf=lambda x: isinstance(x, P))
        else:
            blocks = [_block_specs(cfg, i) for i in range(cfg.num_layers)]
        specs = {
            "wte": P(MODEL_AXIS, None),   # vocab-parallel embedding
            "wpe": P(),
            "blocks": blocks,
            "ln_f": {"scale": P(), "bias": P()},
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, MODEL_AXIS)
        return specs

    # -- forward -------------------------------------------------------
    def _trunk(self, params, tokens, rng=None, train=False, pld_mask=None,
               capture_layers=None):
        """Everything up to (and including) the final layer norm.
        tokens [B, S] int32 -> ([B, S, D] hidden states, MoE aux loss,
        {layer_idx: block output} for capture_layers).

        capture_layers is the TPU-native form of the reference's
        layer-output forward hooks (reference engine.py:227-254): JAX has
        no module hooks, so requested per-block outputs flow out of the
        traced program as explicit extra outputs instead."""
        cfg = self.config
        aux_total = jnp.zeros((), jnp.float32)
        captures = {}
        B, S = tokens.shape
        x = params["wte"][tokens] + params["wpe"][:S][None, :, :]
        if rng is not None:
            rng, sub = jax.random.split(rng)
            x = _dropout(x, cfg.embed_dropout, sub, train)
        x = _constrain(x, cfg, P(DATA_AXIS, SEQ_AXIS, None))

        zig_inv = None
        n_seq = self._stream_zigzag_n()
        if n_seq:
            # ONE layout change for the whole trunk (a static-index
            # gather XLA lowers to a single resharding collective), so
            # every block's ring attention runs mask-free load-balanced;
            # inverted before ln_f — the model's external contract stays
            # contiguous
            if cfg.pipeline_stages > 1:
                raise NotImplementedError(
                    "ring_zigzag + SPMD pipeline is not wired up")
            from ..parallel.ring_attention import zigzag_order

            perm, inv = zigzag_order(S, n_seq)
            zig_inv = jnp.asarray(inv)
            x = _constrain(x[:, jnp.asarray(perm)], cfg,
                           P(DATA_AXIS, SEQ_AXIS, None))

        if cfg.pipeline_stages > 1:
            if capture_layers:
                raise NotImplementedError(
                    "layer-output capture is not supported in SPMD pipeline "
                    "mode (block outputs live on their owning stage)")
            from ..comm.mesh import get_current_mesh
            from ..parallel.pipeline import spmd_pipeline

            x = spmd_pipeline(
                lambda p, h: gpt_block(h, p, cfg, None, train)[0],
                params["blocks"], x, get_current_mesh(),
                num_micro=cfg.pipeline_micro_batches, remat=cfg.remat)
        else:
            block_fn = gpt_block
            if cfg.remat:
                block_fn = jax.checkpoint(
                    gpt_block, static_argnums=(2, 4),
                    policy=jax.checkpoint_policies.nothing_saveable)

            for i, bp in enumerate(params["blocks"]):
                sub = None
                if rng is not None:
                    rng, sub = jax.random.split(rng)
                out, aux = block_fn(x, bp, cfg, sub, train)
                if pld_mask is not None:
                    # progressive layer drop (reference engine.py:972-973):
                    # a dropped layer contributes neither output nor aux
                    aux = jnp.where(pld_mask[i], aux, 0.0)
                    out = jnp.where(pld_mask[i], out, x)
                aux_total = aux_total + aux
                x = out
                if capture_layers is not None and \
                        (capture_layers == "all" or i in capture_layers):
                    # captured in contiguous order even under zigzag
                    captures[i] = x if zig_inv is None else x[:, zig_inv]

        if zig_inv is not None:
            x = _constrain(x[:, zig_inv], cfg, P(DATA_AXIS, SEQ_AXIS, None))
        return (layer_norm(x, params["ln_f"], cfg.layer_norm_eps), aux_total,
                captures)

    def _proj_weight(self, params):
        """[D, V] projection weight in the trunk's compute dtype."""
        if self.config.tie_embeddings:
            return params["wte"].T
        return params["lm_head"]

    def apply(self, params, tokens, rng=None, train=False, pld_mask=None,
              with_aux=False):
        """tokens [B, S] int32 -> logits [B, S, V] (with_aux: also the
        summed MoE load-balancing loss)."""
        x, aux_total, _ = self._trunk(params, tokens, rng=rng, train=train,
                                      pld_mask=pld_mask)
        logits = x @ self._proj_weight(params).astype(x.dtype)
        if with_aux:
            return logits, aux_total
        return logits

    def loss(self, params, batch, rng=None, train=True,
             progressive_layer_drop=False, pld_theta=None,
             capture_layers=None):
        """Next-token cross entropy. batch: (tokens, labels) or dict with
        input_ids/labels; labels == -100 positions are masked (HF parity).

        capture_layers ("all" | iterable of layer indices): also return
        {idx: block output} — the engine's register_forward_hook path."""
        if isinstance(batch, dict):
            tokens = batch["input_ids"]
            labels = batch.get("labels")
        else:
            tokens, labels = batch
        if labels is None:
            tokens, labels = tokens[:, :-1], tokens[:, 1:]

        pld_mask = None
        if progressive_layer_drop and pld_theta is not None and train:
            # per-layer keep gates drawn once per micro step
            if rng is None:
                rng = jax.random.PRNGKey(0)
            rng, sub = jax.random.split(rng)
            pld_mask = jax.random.bernoulli(
                sub, pld_theta, (self.config.num_layers,))

        x, moe_aux, captures = self._trunk(params, tokens, rng=rng,
                                           train=train, pld_mask=pld_mask,
                                           capture_layers=capture_layers)
        valid = (labels >= 0)
        safe_labels = jnp.where(valid, labels, 0)
        B, S, D = x.shape
        nll_sum = _softmax_xent_from_hidden(
            x.reshape(B * S, D), self._proj_weight(params),
            safe_labels.reshape(-1), valid.reshape(-1),
            self.config.loss_chunks, impl=self.config.loss_impl)
        ce = nll_sum / jnp.maximum(jnp.sum(valid), 1)
        if self.config.num_experts > 1 and train:
            # aux applies to the training objective only — eval loss stays
            # pure CE so perplexity comparisons are unbiased
            ce = ce + self.config.moe_aux_loss_weight * moe_aux
        if capture_layers is not None:
            return ce, captures
        return ce

    # -- ZeRO-Infinity streaming protocol ------------------------------
    # (runtime/zero/infinity.py trains larger-than-HBM models by holding
    # only one block's params in device memory at a time; these methods
    # expose the model as embed -> blocks -> head pure stages plus
    # group-wise host init. Reference capability: zero/stage3.py param
    # paging + swap_tensor/partitioned_param_swapper.py.)

    def stream_supported(self) -> bool:
        cfg = self.config
        return (cfg.num_experts == 1 and cfg.pipeline_stages == 1
                and cfg.dropout == 0.0 and cfg.embed_dropout == 0.0)

    def _stream_zigzag_n(self) -> int:
        """seq-axis size when zigzag layout is active, else 0 — THE
        gating rule, shared by the trunk's one-shot layout change
        (_trunk) and the streamed boundary (stream_embed permutes,
        stream_head_loss inverts), so the two paths cannot drift and
        long-context + larger-than-HBM compose."""
        cfg = self.config
        if not (cfg.sequence_parallel
                and cfg.sequence_parallel_impl == "ring_zigzag"):
            return 0
        from ..comm.mesh import get_current_mesh

        n = get_current_mesh().axis_size(SEQ_AXIS)
        return n if n > 1 else 0

    def stream_init(self, rng):
        """Yield (group_name, host_numpy_subtree) with only ONE group ever
        materialized on device — init for models that don't fit in HBM."""
        import numpy as _np

        cfg = self.config
        keys = jax.random.split(rng, cfg.num_layers + 3)
        to_host = lambda t: jax.tree_util.tree_map(
            lambda a: _np.asarray(a), t)

        def embed_init(k0, k1):
            return {"wte": init_wte(k0, cfg), "wpe": init_wpe(k1, cfg)}

        yield "embed", to_host(jax.jit(embed_init)(keys[0], keys[1]))
        for i in range(cfg.num_layers):
            yield f"block:{i}", to_host(
                jax.jit(lambda k, i=i: _init_block(k, cfg, i))(keys[2 + i]))
        head = {"ln_f": init_final_ln(cfg)}
        if not cfg.tie_embeddings:
            head["lm_head"] = jax.jit(
                lambda k: init_lm_head(k, cfg))(keys[-1])
        yield "head", to_host(head)

    def stream_groups(self, params):
        """Disjoint group cover of a full params tree (inverse of
        assemble_groups)."""
        groups = [("embed", {"wte": params["wte"], "wpe": params["wpe"]})]
        for i, bp in enumerate(params["blocks"]):
            groups.append((f"block:{i}", bp))
        head = {"ln_f": params["ln_f"]}
        if not self.config.tie_embeddings:
            head["lm_head"] = params["lm_head"]
        groups.append(("head", head))
        return groups

    def assemble_groups(self, groups: Dict[str, Any]):
        params = {"wte": groups["embed"]["wte"],
                  "wpe": groups["embed"]["wpe"],
                  "blocks": [groups[f"block:{i}"]
                             for i in range(self.config.num_layers)],
                  "ln_f": groups["head"]["ln_f"]}
        if not self.config.tie_embeddings:
            params["lm_head"] = groups["head"]["lm_head"]
        return params

    def stream_embed(self, embed_p, tokens):
        S = tokens.shape[1]
        x = embed_p["wte"][tokens] + embed_p["wpe"][:S][None, :, :]
        n = self._stream_zigzag_n()
        if n:
            from ..parallel.ring_attention import zigzag_order

            perm, _ = zigzag_order(S, n)
            x = _constrain(x[:, jnp.asarray(perm)], self.config,
                           P(DATA_AXIS, SEQ_AXIS, None))
        return x

    def stream_block(self, block_p, x):
        return gpt_block(x, block_p, self.config, None, True)[0]

    def stream_head_loss(self, head_p, wte_or_lm_head, x, labels, valid):
        """ln_f + fused projection CE. `wte_or_lm_head`: the tied wte
        ([V, D]) or lm_head ([D, V]) — tied grads flow to the caller.
        Under zigzag SP, x arrives in the zigzag layout (stream_embed
        permuted it) and is inverted here — labels stay contiguous, the
        same contract as the trunk's pre-ln_f inverse."""
        cfg = self.config
        n = self._stream_zigzag_n()
        if n:
            from ..parallel.ring_attention import zigzag_order

            _, inv = zigzag_order(x.shape[1], n)
            x = _constrain(x[:, jnp.asarray(inv)], cfg,
                           P(DATA_AXIS, SEQ_AXIS, None))
        x = layer_norm(x, head_p["ln_f"], cfg.layer_norm_eps)
        w = (wte_or_lm_head.T if cfg.tie_embeddings else wte_or_lm_head)
        B, S, D = x.shape
        nll = _softmax_xent_from_hidden(
            x.reshape(B * S, D), w, labels.reshape(-1), valid.reshape(-1),
            cfg.loss_chunks, impl=cfg.loss_impl)
        return nll / jnp.maximum(jnp.sum(valid), 1)

    # -- convenience ---------------------------------------------------
    def num_params(self, params=None) -> int:
        if params is None:
            shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
            return sum(int(np_prod(l.shape))
                       for l in jax.tree_util.tree_leaves(shapes))
        return sum(int(l.size) for l in jax.tree_util.tree_leaves(params))


def np_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out
