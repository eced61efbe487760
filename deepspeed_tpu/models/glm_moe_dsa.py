"""GLM-5.2 (`model_type` `glm_moe_dsa`): latent attention with a query
low-rank path, a LEARNED selection of the rows each query attends
("DSA": a lightning indexer scores every earlier token, the `index_topk`
largest are attended) that some layers compute and the others take over
("IndexShare"), and sigmoid-routed experts chosen under a selection bias
(`noaux_tc`) beside one shared expert.

Built from models/deepseek_v2.py's pieces (the latent row, its two
attention forms, RMSNorm) and moe/dropless.py (routing, a chip's share
of the experts, the three routed products).  A block is pre-norm,
RMSNorm with a gain, the residual stream float32.

Attention, every layer: c_q = RMSNorm(h W_q_a), [q_nope | q_rope] a
head = c_q W_q_b, [c | k_r] = h W_kv_a, c RMS-normed, q_rope and k_r
rotated in INTERLEAVED pairs (dims 2i, 2i + 1), no scaling of the
frequencies.  The cached row is [c | k_r]; scores are (q_nope.k_nope +
q_rope.k_r) (nope + rope)^-1/2 over the rows the query's selection
holds.

Indexer, layers marked "full": q^I = c_q W^I_q (Hi heads of Di), ONE
key a token k^I = LayerNorm(h W^I_k) (scale and bias) — a second cached
row —, the first `rope` dims of both rotated like the attention's,
w = h W^I_w Hi^-1/2 Di^-1/2; the index score of query t for row s <= t
is sum_j w_tj ReLU(q^I_tj . k^I_s), float32 (`index_scores`), and the
selection S_t the `index_topk` rows with the largest scores, ties to
the lower position, every row while t < index_topk (`select_mask`).  A
layer marked "shared" has no indexer weights and no index keys and
attends S_t of the nearest "full" layer before it.

FFN: the first `first_k_dense` layers a SiLU-gated FFN; the others
s = sigmoid(h W_r) in float32, the `top_k` experts with the largest
s + b chosen (b the layer's `select_bias`, which does not weigh),
weights s_i over the chosen's sum times `route_scale`, plus one shared
expert (models/cohere2_moe.py `expert_ffn`, which reads the layer spec).
`experts_held` > 0: this chip holds that many experts from `first_expert`
on (moe/dropless.py `held_assignments`); the router keeps all
`num_experts` outputs.

The multi-token-prediction layer of the published model is not built.
The serving engine runs the model through `layer_spec()`
(serving/layers.py, serving/sparse.py); `apply` is the uncached forward
the tests compare with `benchmarks/reference/glm_moe_dsa.py`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import cohere2_moe
from .deepseek_v2 import (attend_absorbed, attend_expanded, latent_project,
                          rms_norm_plain, rope_part)
from .evabyte import matmul32, silu_gated_ffn
from .generation import kth_largest
from .gpt import layer_norm
from .layer_spec import LayerSpec


@dataclasses.dataclass
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    max_seq_len: int = 4096
    num_layers: int = 78
    num_heads: int = 64
    d_model: int = 6144
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6     # the index key's LayerNorm
    # "full" | "shared" of every layer; () derives the published list
    # from the two numbers below
    indexer_types: tuple = ()
    index_topk_freq: int = 4
    index_skip_topk_offset: int = 3
    d_ff: int = 12288                # the leading dense layers' width
    first_k_dense: int = 3
    num_experts: int = 256
    top_k: int = 8
    num_shared_experts: int = 1
    d_expert: int = 2048
    route_scale: float = 2.5
    experts_held: int = 0            # 0: every expert is held here
    first_expert: int = 0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 8e6
    # seeded weights only: every matrix N(0, init_std); the router's own
    # scale decides how sharply it picks, the selection bias's
    # (N(0, bias_std)) how often it overrules the scores, and W_q_b's
    # (`query_std`, 0: init_std) how sharply a query picks among its rows
    # — at init_std the softmax over some thousand rows is flat and
    # WHICH rows were selected hardly shows in the output
    init_std: float = 0.02
    router_std: float = 0.02
    bias_std: float = 0.1
    query_std: float = 0.0
    # the embedding's (0: init_std): beside attention's output it decides
    # how far what the first layer attends moves the stream
    embed_std: float = 0.0
    param_dtype: Any = jnp.float32
    # what the pieces of models/deepseek_v2.py read
    yarn: Optional[Any] = None
    rope_interleave: bool = True

    def __post_init__(self):
        if self.qk_rope_head_dim % 2 or \
                self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                "rotary positions need an even qk_rope_head_dim no wider "
                "than an index head")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} must lie in 1.."
                             f"num_experts ({self.num_experts})")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError("first_k_dense must lie in 0..num_layers")
        if not self.indexer_types:
            off, freq = self.index_skip_topk_offset, self.index_topk_freq
            self.indexer_types = tuple(
                "full" if i < off or (i - off + 1) % freq == 0 else "shared"
                for i in range(self.num_layers))
        self.indexer_types = tuple(self.indexer_types)
        if len(self.indexer_types) != self.num_layers or \
                self.indexer_types[0] != "full" or any(
                    t not in ("full", "shared") for t in self.indexer_types):
            raise ValueError(
                f"indexer_types says \"full\" or \"shared\" of each of the "
                f"{self.num_layers} layers, a \"full\" one first; got "
                f"{self.indexer_types}")
        if self.experts_held < 0 or self.first_expert < 0 or \
                self.first_expert + self.experts_held > self.num_experts:
            raise ValueError(
                f"a share of the experts is experts_held >= 0 experts from "
                f"first_expert on, inside the router's {self.num_experts}")

    @property
    def head_dim(self) -> int:
        """A query's width: [q_nope | q_rope]."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A cache row: [c | k_r], one a token for all heads."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# ---------------------------------------------------------------------------
# the pieces (shared with serving/sparse.py)
# ---------------------------------------------------------------------------


def index_project(cfg, p, h, c_q, positions, dtype):
    """The indexer's side of a call: h [B, T, D] and the queries' latent
    c_q [B, T, q_lora_rank] at positions [B, T] -> (q^I [B, T, Hi, Di]
    and the tokens' index keys k^I [B, T, Di] at `dtype`, the first
    `rope` dims of both rotated; w [B, T, Hi] float32, the heads'
    weights with both scales in)."""
    B, T, _ = h.shape
    Hi, Di, dr = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    turn = lambda x: jnp.concatenate(
        [rope_part(x[..., :dr], positions, cfg.rope_theta, None, True),
         x[..., dr:]], axis=-1)
    q = turn(matmul32(c_q, p["q"]).reshape(B, T, Hi, Di))
    k = turn(layer_norm(matmul32(h, p["k"]), p["k_norm"],
                        cfg.index_norm_eps))
    w = matmul32(h, p["w"]) * (Hi ** -0.5 * Di ** -0.5)
    return q.astype(dtype), k.astype(dtype), w


def index_scores(q, w, keys):
    """I[b, t, s] = sum_j w[b, t, j] ReLU(q[b, t, j] . keys[b, s]):
    q [B, T, Hi, Di], w [B, T, Hi], keys [B, K, Di] -> [B, T, K]
    float32."""
    dots = jnp.einsum("bthd,bkd->bthk", q, keys,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bthk,bth->btk", jax.nn.relu(dots), w)


def select_mask(scores, visible, topk: int):
    """Which rows each query attends: scores [..., K] float32 and
    visible [..., K] (the rows the query may see at all) -> bool
    [..., K]: the `topk` visible rows with the largest scores — every
    visible row where there are no more than that —, ties at the
    threshold to the lower row; the threshold is a row's k-th largest
    score, found without a sort (models/generation.py `kth_largest`)."""
    shape = scores.shape
    masked = jnp.where(visible, scores, -jnp.inf).reshape(-1, shape[-1])
    seen = visible.reshape(masked.shape)
    k = jnp.clip(jnp.sum(seen, axis=-1, dtype=jnp.int32), 1, topk)
    thr = kth_largest(masked, k)[:, None]
    above = masked > thr
    ties = (masked == thr) & seen
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    # the running count only where some row has more equals than room
    chosen = jax.lax.cond(
        jnp.any(jnp.sum(ties, axis=-1, dtype=jnp.int32) > room),
        lambda: above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                                 <= room[:, None])),
        lambda: above | ties)
    return chosen.reshape(shape)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class GlmMoeDsa:
    """GLM-5.2's language model as the serving engine and the tests take
    it: `init` from a key, `apply` (uncached), `layer_spec` (what serving
    builds its programs from)."""

    def __init__(self, config: GlmMoeDsaConfig):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        return LayerSpec(
            norm="rmsnorm", positions="rope", attention="latent",
            ffn="routed_experts", head="untied", eps=c.rms_norm_eps,
            rope_theta=c.rope_theta, top_k=c.top_k,
            dense_layers=c.first_k_dense, latent_width=c.latent_width,
            scoring="sigmoid", renormalize=True,
            experts_held=c.experts_held, first_expert=c.first_expert,
            select_bias=True, route_scale=c.route_scale,
            layer_indexers=c.indexer_types, index_topk=c.index_topk,
            index_heads=c.index_heads,
            index_width=c.index_head_dim).validate()

    def init(self, rng):
        c = self.config
        d, dt, std = c.d_model, c.param_dtype, c.init_std
        H, f = c.num_heads, c.d_expert
        held = c.experts_held or c.num_experts

        def normal(key, shape, scale=std):
            return (jax.random.normal(key, shape) * scale).astype(dt)

        def gated(keys, width, lead=()):
            return {"gate": normal(keys[0], lead + (d, width)),
                    "up": normal(keys[1], lead + (d, width)),
                    "down": normal(keys[2], lead + (width, d))}

        def block(key, i):
            k = jax.random.split(key, 16)
            if i < c.first_k_dense:
                mlp = gated(k[5:8], c.d_ff)
            else:
                mlp = {"router": normal(k[5], (d, c.num_experts),
                                        c.router_std),
                       "select_bias": (jax.random.normal(
                           k[12], (c.num_experts,)) * c.bias_std
                       ).astype(jnp.float32),
                       "experts": gated(k[6:9], f, (held,)),
                       "shared": gated(k[9:12], c.num_shared_experts * f)}
            attn = {"q_a": normal(k[0], (d, c.q_lora_rank)),
                    "q_norm": {"scale": jnp.ones((c.q_lora_rank,), dt)},
                    "q_b": normal(k[1], (c.q_lora_rank, H * c.head_dim),
                                  c.query_std or std),
                    "kv_a": normal(k[2], (d, c.latent_width)),
                    "kv_norm": {"scale": jnp.ones((c.kv_lora_rank,), dt)},
                    "kv_b": normal(k[3], (c.kv_lora_rank, H * (
                        c.qk_nope_head_dim + c.v_head_dim))),
                    "o": normal(k[4], (H * c.v_head_dim, d))}
            if c.indexer_types[i] == "full":
                Di = c.index_head_dim
                attn["indexer"] = {
                    "q": normal(k[13], (c.q_lora_rank, c.index_heads * Di)),
                    "k": normal(k[14], (d, Di)),
                    "k_norm": {"scale": jnp.ones((Di,), dt),
                               "bias": jnp.zeros((Di,), dt)},
                    "w": normal(k[15], (d, c.index_heads))}
            return {"ln1": {"scale": jnp.ones((d,), dt)}, "attn": attn,
                    "ln2": {"scale": jnp.ones((d,), dt)}, "mlp": mlp}

        keys = jax.random.split(rng, c.num_layers + 2)
        return {
            "wte": normal(keys[0], (c.vocab_size, d), c.embed_std or std),
            "blocks": [block(k, i) for i, k in enumerate(keys[2:])],
            "ln_f": {"scale": jnp.ones((d,), dt)},
            "lm_head": normal(keys[1], (d, c.vocab_size)),
        }

    def apply(self, params, tokens, absorbed: bool = False,
              return_selected: bool = False):
        """tokens [B, S] int32 -> logits [B, S, vocab] float32, no
        cache; `absorbed` takes the decode path's products.  With
        `return_selected` also the selections [B, S, S] bool of the
        "full" layers, in layer order."""
        c, spec = self.config, self.layer_spec()
        B, S = tokens.shape
        x = params["wte"][tokens].astype(jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        causal = jnp.broadcast_to(
            jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], (B, S, S))
        attend = attend_absorbed if absorbed else attend_expanded
        chosen, selections = None, []
        for i, p in enumerate(params["blocks"]):
            h = rms_norm_plain(x, p["ln1"], c.rms_norm_eps)
            q_nope, q_rope, rows, c_q = latent_project(
                c, p["attn"], h, positions, c.param_dtype, with_cq=True)
            if c.indexer_types[i] == "full":
                with jax.named_scope("dsa_index"):
                    q_i, k_i, w = index_project(
                        c, p["attn"]["indexer"], h, c_q, positions,
                        c.param_dtype)
                    scores = index_scores(q_i, w, k_i)
                with jax.named_scope("dsa_select"):
                    chosen = select_mask(scores, causal, c.index_topk)
                selections.append(chosen)
            with jax.named_scope("dsa_attend"):
                a = attend(c, p["attn"]["kv_b"], q_nope, q_rope, rows,
                           chosen)
            x = x + matmul32(a, p["attn"]["o"])
            h = rms_norm_plain(x, p["ln2"], c.rms_norm_eps)
            if i < c.first_k_dense:
                x = x + silu_gated_ffn(p["mlp"], h)
            else:
                x = x + cohere2_moe.expert_ffn(spec, c, p["mlp"], h)[0]
        h = rms_norm_plain(x, params["ln_f"], c.rms_norm_eps)
        out = matmul32(h, params["lm_head"])
        return (out, selections) if return_selected else out

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
