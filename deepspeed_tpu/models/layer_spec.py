"""The layer spec a model hands to the serving engine: which norm,
positions, attention and FFN its block is made of, and which rows its
cache needs.  `serving/layers.py` assembles embed, block and head from
it, so `serving/programs.py` knows no model family by name (ROADMAP D1,
the serving third: training and `generation.py` still write their own
block).

A served model provides `layer_spec() -> LayerSpec`, a config with
`num_layers`, `num_heads`, `head_dim`, `vocab_size`, `max_seq_len`,
`param_dtype`, and a parameter tree with `blocks` (one entry a layer,
laid out as its kinds below say), `wte` and what its positions, final
norm and head need.
"""

from __future__ import annotations

from typing import NamedTuple

NORMS = ("layernorm", "rmsnorm_unit_offset", "rmsnorm")
POSITIONS = ("learned", "rope")
ATTENTIONS = ("paged", "eva", "latent")
FFNS = ("gelu_mlp", "silu_gated", "routed_experts")
HEADS = ("tied", "untied")


class LayerSpec(NamedTuple):
    """One kind of layer, repeated `num_layers` times; the first
    `dense_layers` of them may keep a plain gated FFN where the others
    route.

    norm       "layernorm" (scale, bias) | "rmsnorm_unit_offset" (the
               scale is 1 + g) | "rmsnorm" (the scale is the gain w)
    positions  "learned" (a `wpe` table added to the embedding) | "rope"
               (rotary on q and k: over the whole head, or, with latent
               attention, over the rotary part of it, with the model's
               YaRN frequencies)
    attention  "paged": causal softmax over every cached position; the
               cache holds one exact K/V row a token for the request's
               whole life.  "eva": exact rows for the open window of
               `window` tokens, one summary row per `chunk` tokens of
               every closed window, one softmax over both.
               "latent": causal softmax over every cached position, and
               the cache holds ONE row a token for all heads, of
               `latent_width` values ([latent | rotary key]); prefill
               expands a request's rows to per-head keys and values,
               decode absorbs the expansion into the query and the
               output (models/deepseek_v2.py).
    ffn        "gelu_mlp" (fc1, tanh GELU, fc2, biases) | "silu_gated"
               | "routed_experts" (a float32 softmax router, the top k
               experts a token unrenormalised, every assignment
               computed — moe/dropless.py — plus shared experts; the
               first `dense_layers` layers are "silu_gated")
    head       "tied" (wte transposed) | "untied" (`lm_head`)
    eps        the norm's epsilon
    """

    norm: str
    positions: str
    attention: str
    ffn: str
    head: str
    eps: float
    rope_theta: float = 0.0
    window: int = 0          # eva: tokens of exact keys
    chunk: int = 0           # eva: tokens a summary row stands for
    sample_vocab: int = 0    # logits the next token is drawn from
    #                          (0: all; a multi-head output samples its
    #                          first head)
    fp32_logits: bool = False  # head product in float32, full precision
    latent_width: int = 0    # latent: values of a token's one cache row
    top_k: int = 0           # routed_experts: experts a token chooses
    dense_layers: int = 0    # routed_experts: leading layers that keep
    #                          a plain gated FFN

    def validate(self) -> "LayerSpec":
        for value, kinds in ((self.norm, NORMS), (self.positions, POSITIONS),
                             (self.attention, ATTENTIONS), (self.ffn, FFNS),
                             (self.head, HEADS)):
            if value not in kinds:
                raise ValueError(
                    f"layer spec: {value!r} is not one of {kinds}")
        if self.attention == "eva":
            if self.chunk < 1 or self.window < self.chunk \
                    or self.window % self.chunk:
                raise ValueError(
                    f"layer spec: eva attention needs window "
                    f"({self.window}) a multiple of chunk ({self.chunk})")
        if (self.attention == "latent") != (self.latent_width > 0):
            raise ValueError(
                f"layer spec: latent attention, and nothing else, names "
                f"its row's latent_width (got {self.attention!r}, "
                f"{self.latent_width})")
        if (self.ffn == "routed_experts") != (self.top_k > 0) or (
                self.dense_layers and not self.top_k):
            raise ValueError(
                f"layer spec: a routed_experts FFN, and nothing else, "
                f"names the top_k experts a token chooses and may keep "
                f"leading dense_layers (got {self.ffn!r}, top_k "
                f"{self.top_k}, dense_layers {self.dense_layers})")
        return self
