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

NORMS = ("layernorm", "rmsnorm_unit_offset", "rmsnorm", "layernorm_gain")
POSITIONS = ("learned", "rope", "per_layer", "none")
ATTENTIONS = ("paged", "eva", "latent", "grouped")
FFNS = ("gelu_mlp", "silu_gated", "routed_experts")
HEADS = ("tied", "untied")
RESIDUALS = ("sequential", "parallel", "single")
SCORINGS = ("softmax", "sigmoid")
SHARED = ("sum", "average", "gated")
MIXERS = ("attention", "ssm", "gdn", "conv", "none")


class LayerSpec(NamedTuple):
    """One kind of layer, repeated `num_layers` times — or, with
    "grouped" attention, a pattern of layers repeated: `layer_windows`
    and `layer_positions` give layer l its window and its positions at
    index l mod the pattern's length, and `layer_mixers` says which of
    them mix tokens by attention and which by a recurrence over a state
    a request keeps (state-space, or the gated delta rule).
    The first `dense_layers` layers may keep a plain gated FFN where the
    others route.

    norm       "layernorm" (scale, bias) | "rmsnorm_unit_offset" (the
               scale is 1 + g) | "rmsnorm" (the scale is the gain w) |
               "layernorm_gain" (mean subtracted, a gain and no bias)
    positions  "learned" (a `wpe` table added to the embedding) | "rope"
               (rotary on q and k: over the whole head, or, with latent
               attention, over the rotary part of it, with the model's
               YaRN frequencies) | "per_layer" (`layer_positions` says
               of each layer "rope" — over the whole head, interleaved
               pairing, or, with `rope_halves`, dims i and i + half
               of the first `rotary_dim` values of it — or "none": no
               positions at all)
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
               "grouped": causal softmax over exact rows of `kv_heads`
               keys and values a token, query head n reading K/V head
               n // (num_heads / kv_heads); a layer whose entry of
               `layer_windows` is w > 0 attends the last w positions
               only (the query's own among them) and its rows may live
               in a ring of `window + prefill_chunk` rows a request
               (serving/kv_cache.py's group "window"); 0 is every
               cached position (models/cohere2_moe.py).  Three
               additions, each off unless the spec says so
               (models/qwen3_next.py): `attn_gate` — the query
               projection is twice as wide, [q | gate] a head, and the
               attended values are multiplied by sigmoid(gate) before
               the output projection; `qk_norm` — q and k are
               RMS-normed over the head (the gain 1 + g, `q_norm` and
               `k_norm`) before they are rotated; `rotary_dim` — a
               rotating layer turns the first `rotary_dim` values of a
               head only (0: the whole head).
    indexers   `layer_indexers` (latent attention only; empty: every
               cached row is attended) says of layer l, at index l mod
               its length, "full" — the layer computes a learned
               selection: an indexer of `index_heads` heads of
               `index_width` scores the call's queries against ONE
               cached index key a token (a second row of `index_width`
               values the layer owns beside its latent row), and a
               query attends the `index_topk` rows with the largest
               scores (every row while it has no more than that) — or
               "shared": no indexer and no index keys, the layer attends
               the selection of the nearest "full" layer before it, made
               in the same call (models/glm_moe_dsa.py).
    mixers     `layer_mixers` (empty: every layer attends) gives layer
               l, at index l mod its length, "attention" (the kind
               above) or "ssm": a Mamba-2 mixer (models/
               granite_hybrid.py) of `ssm_heads` heads of
               `ssm_head_dim` over `ssm_groups` groups of `ssm_state`
               state values (head h reads the B and C of group
               h // (ssm_heads / ssm_groups), and the gated norm is
               taken over each group's values apart; one group: over
               all of them), behind a causal depthwise convolution of
               `ssm_conv` taps.  Such a layer owns no cache rows: it
               keeps, a request, one float32 state [ssm_heads,
               ssm_head_dim, ssm_state] and the convolution's last
               `ssm_conv - 1` inputs — a prefill chunk scans from the
               state and leaves it behind, `ssm_chunk` positions at a
               time; a decode step moves it on by one token.
               "gdn": a gated delta-rule mixer (models/qwen3_next.py)
               of `gdn_value_heads` heads of `gdn_value_dim` values on
               `gdn_key_heads` keys and queries of `gdn_key_dim` (value
               head j on key head j // (value heads / key heads)),
               behind a causal depthwise convolution of `gdn_conv` taps
               over [q | k | v].  It keeps, a request, one float32 state
               [gdn_value_heads, gdn_key_dim, gdn_value_dim] and the
               convolution's last `gdn_conv - 1` inputs; a prefill chunk
               takes `gdn_chunk` positions at a time.  A pattern has
               state layers of one kind (`state_shapes` says what ONE
               slot keeps for a layer of it).
               "conv": a gated short convolution (models/lfm2_moe.py):
               a causal depthwise convolution of `conv_taps` taps over
               `conv_channels` channels, gated on both sides, with no
               activation and no recurrence.  It keeps, a request, the
               convolution's last `conv_taps - 1` inputs and NOTHING
               else — one array, no float32 state, no chunk size.
               `STATE_MIXERS` below is the one table of these kinds:
               what a slot keeps, the mix function, the counters'
               family and the step kernel of each.  "none" (with the
               "single" residual alone): the layer has no mixer — it is
               its FFN and nothing else, and owns neither rows nor a
               state.
    ffn        "gelu_mlp" (fc1, tanh GELU, fc2, biases) | "silu_gated"
               | "routed_experts" (a float32 router — `scoring`
               "softmax" over all experts or "sigmoid" of each — the top
               k experts a token, their weights as they are or, with
               `renormalize`, over their sum; every assignment computed
               (with `select_bias` the k are chosen by the scores plus a
               bias a layer holds, which does not weigh; `route_scale`
               multiplies the weights last) — moe/dropless.py — among
               the `experts_held` experts from
               `first_expert` on that this chip holds (0: all); plus
               shared experts (where the tree holds any: `shared`),
               their outputs summed or, with `shared`
               "average", their mean, or, with "gated", times the
               sigmoid of the token's product with `shared_gate`
               [D, 1]; the first `dense_layers` layers are
               "silu_gated").  What an expert, and the shared
               expert with it, is — three matrices, down(silu(gate h) *
               up h), or two, down(relu(up h) ** 2) — is what the tree
               holds (kernels/expert_form.py), not a field here
    head       "tied" (wte transposed) | "untied" (`lm_head`)
    residual   "sequential" (x + attn(norm1 x), then + ffn(norm2 of
               that)) | "parallel" (one norm: x + attn(h) + ffn(h)) |
               "single" (one norm and ONE part a layer, x + part(norm1
               x): the mixer `layer_mixers` names, or, where it says
               "none", the FFN; models/nemotron_h.py)
    eps        the norm's epsilon
    scalars    `embed_scale` multiplies the embedding, `residual_scale`
               every branch before it is added to the stream,
               `logit_divisor` divides the logits, and `attn_scale`
               (0: head_dim ** -0.5) multiplies grouped attention's
               scores.
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
    kv_heads: int = 0        # grouped: K/V heads of a cache row
    layer_windows: tuple = ()    # grouped: the pattern's windows (0: full)
    layer_positions: tuple = ()  # per_layer: the pattern's "rope" | "none"
    residual: str = "sequential"
    scoring: str = "softmax"     # routed_experts: the router's scores
    renormalize: bool = False    # routed_experts: weights over their sum
    shared: str = "sum"          # routed_experts: the shared experts
    experts_held: int = 0        # routed_experts: experts held here (0: all)
    first_expert: int = 0        # routed_experts: the first one held
    layer_mixers: tuple = ()     # the pattern's "attention" | "ssm" | "gdn"
    #                              | "conv" | "none" (single: the layer is
    #                              its FFN)
    ssm_heads: int = 0           # ssm: heads of the recurrence
    ssm_head_dim: int = 0        # ssm: values a head
    ssm_state: int = 0           # ssm: state values (B and C's width)
    ssm_conv: int = 0            # ssm: taps of the causal convolution
    ssm_chunk: int = 0           # ssm: positions the scan takes at once
    ssm_groups: int = 1          # ssm: groups of heads, each with a B and C
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float = 0.0      # grouped: 0 is head_dim ** -0.5
    logit_divisor: float = 1.0
    layer_indexers: tuple = ()   # latent: the pattern's "full" | "shared"
    index_topk: int = 0          # indexers: rows a query attends at most
    index_heads: int = 0         # indexers: heads of the indexer
    index_width: int = 0         # indexers: values of a cached index key
    select_bias: bool = False    # routed_experts: a bias chooses the top k
    route_scale: float = 1.0     # routed_experts: times the weights
    gdn_key_heads: int = 0       # gdn: heads of keys and queries
    gdn_value_heads: int = 0     # gdn: heads of values (and of the state)
    gdn_key_dim: int = 0         # gdn: values of a key
    gdn_value_dim: int = 0       # gdn: values of a value
    gdn_conv: int = 0            # gdn: taps of the causal convolution
    gdn_chunk: int = 0           # gdn: positions the scan takes at once
    attn_gate: bool = False      # grouped: [q | gate] a head, sigmoid gate
    qk_norm: bool = False        # grouped: q and k RMS-normed over the head
    rotary_dim: int = 0          # grouped: values of a head that rotate (0: all)
    rope_halves: bool = False    # grouped: pairs i, i + half (not 2i, 2i + 1)
    conv_taps: int = 0           # conv: taps of the causal convolution
    conv_channels: int = 0       # conv: its channels (the model's width)
    renorm_eps: float = 0.0      # routed_experts: added to the chosen
    #                              weights' sum before it divides them

    def window_of(self, layer: int) -> int:
        """The window of layer `layer` (0: every cached position)."""
        if not self.layer_windows:
            return 0
        return self.layer_windows[layer % len(self.layer_windows)]

    def rotates(self, layer: int) -> bool:
        """Whether layer `layer` of a "per_layer" spec rotates q and k."""
        return bool(self.layer_positions) and self.layer_positions[
            layer % len(self.layer_positions)] == "rope"

    def mixer_of(self, layer: int) -> str:
        """"attention", a kind of `STATE_MIXERS`, or "none": how layer
        `layer` mixes tokens."""
        if not self.layer_mixers:
            return "attention"
        return self.layer_mixers[layer % len(self.layer_mixers)]

    def indexer_of(self, layer: int):
        """None (every cached row is attended), or "full" | "shared":
        whether layer `layer` selects the rows its queries attend or
        takes the selection of the "full" layer before it."""
        if not self.layer_indexers:
            return None
        return self.layer_indexers[layer % len(self.layer_indexers)]

    def index_layers(self, num_layers: int) -> tuple:
        """The layers of `num_layers` that own index-key rows."""
        return tuple(i for i in range(num_layers)
                     if self.indexer_of(i) == "full")

    def has_ffn(self, layer: int) -> bool:
        """Whether layer `layer` has an FFN: every layer, but under the
        "single" residual only those without a mixer."""
        return self.residual != "single" or self.mixer_of(layer) == "none"

    def routed_layers(self, num_layers: int) -> tuple:
        """The layers of `num_layers` whose FFN routes."""
        if self.ffn != "routed_experts":
            return ()
        return tuple(i for i in range(self.dense_layers, num_layers)
                     if self.has_ffn(i))

    @property
    def state_mixer(self):
        """None, or the pattern's one kind of `STATE_MIXERS`: how its
        layers with a state mix tokens."""
        return next((m for m in self.layer_mixers if m in STATE_MIXERS),
                    None)

    @property
    def has_state(self) -> bool:
        """Whether some layer keeps a state a request beside (or in
        place of) cache rows."""
        return self.state_mixer is not None

    def state_layers(self, num_layers: int) -> tuple:
        """The layers of `num_layers` that keep a state a request and
        no cache rows."""
        return tuple(i for i in range(num_layers)
                     if self.mixer_of(i) in STATE_MIXERS)

    def row_layers(self, num_layers: int) -> tuple:
        """The layers of `num_layers` that attend, and so own cache
        rows; a layer in neither this nor `state_layers` owns nothing."""
        return tuple(i for i in range(num_layers)
                     if self.mixer_of(i) == "attention")

    @property
    def state_shapes(self) -> tuple:
        """What ONE slot keeps for one layer with a state: ((shape,
        dtype or None: the cache's), ...) — a float32 state and the
        convolution's last inputs, or those inputs alone; () where no
        layer has one."""
        kind = self.state_mixer
        return STATE_MIXERS[kind].keeps(self) if kind else ()

    @property
    def state_chunk(self) -> int:
        """Positions the state layers' scan takes at once (0: none)."""
        kind = self.state_mixer
        return STATE_MIXERS[kind].chunk(self) if kind else 0

    @property
    def gdn_conv_width(self) -> int:
        """Channels of the delta mixer's convolution: [q | k | v]."""
        return 2 * self.gdn_key_heads * self.gdn_key_dim + \
            self.gdn_value_heads * self.gdn_value_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels of the convolution: the heads' values, and the B
        and C of every group."""
        return self.ssm_heads * self.ssm_head_dim + \
            2 * self.ssm_groups * self.ssm_state

    @property
    def held(self):
        """None, or (first expert held, experts held) of a share."""
        if not self.experts_held:
            return None
        return self.first_expert, self.experts_held

    def validate(self) -> "LayerSpec":
        for value, kinds in ((self.norm, NORMS), (self.positions, POSITIONS),
                             (self.attention, ATTENTIONS), (self.ffn, FFNS),
                             (self.head, HEADS), (self.residual, RESIDUALS),
                             (self.scoring, SCORINGS), (self.shared, SHARED)):
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
        if (self.attention == "grouped") != (self.kv_heads > 0) or (
                self.layer_windows and self.attention != "grouped"):
            raise ValueError(
                f"layer spec: grouped attention, and nothing else, names "
                f"the kv_heads of its row and may give layer_windows (got "
                f"{self.attention!r}, kv_heads {self.kv_heads}, "
                f"layer_windows {self.layer_windows})")
        if any(int(w) < 0 for w in self.layer_windows):
            raise ValueError(
                f"layer spec: a layer's window is 0 (full) or a count of "
                f"positions, got {self.layer_windows}")
        if (self.positions == "per_layer") != bool(self.layer_positions) \
                or any(p not in ("rope", "none")
                       for p in self.layer_positions):
            raise ValueError(
                f"layer spec: per_layer positions, and nothing else, say "
                f"\"rope\" or \"none\" of each layer of the pattern (got "
                f"{self.positions!r}, {self.layer_positions})")
        routed_only = (self.scoring != "softmax" or self.renormalize
                       or self.shared != "sum" or self.experts_held
                       or self.first_expert or self.select_bias
                       or self.route_scale != 1.0)
        if routed_only and self.ffn != "routed_experts":
            raise ValueError(
                f"layer spec: scoring, renormalize, shared, experts_held, "
                f"first_expert, select_bias and route_scale describe a "
                f"routed_experts FFN (got {self.ffn!r})")
        if self.renorm_eps < 0 or (self.renorm_eps
                                   and not self.renormalize):
            raise ValueError(
                f"layer spec: renorm_eps >= 0 is added to the sum that "
                f"renormalize divides the chosen weights by (got "
                f"renorm_eps {self.renorm_eps}, renormalize "
                f"{self.renormalize})")
        sizes = (self.index_topk, self.index_heads, self.index_width)
        if any(k not in ("full", "shared") for k in self.layer_indexers) \
                or bool(self.layer_indexers) != all(n > 0 for n in sizes) \
                or (not self.layer_indexers and any(sizes)) \
                or (self.layer_indexers
                    and (self.attention != "latent"
                         or self.layer_indexers[0] != "full")):
            raise ValueError(
                f"layer spec: layer_indexers says \"full\" or \"shared\" "
                f"of each layer of a latent-attention pattern, a \"full\" "
                f"one first, and with it, and with nothing else, go "
                f"index_topk, index_heads and index_width (got "
                f"{self.layer_indexers} with {self.attention!r} attention, "
                f"{sizes})")
        if self.experts_held < 0 or self.first_expert < 0 or (
                self.first_expert and not self.experts_held):
            raise ValueError(
                f"layer spec: a share of the experts is experts_held > 0 "
                f"experts from first_expert >= 0 on (got "
                f"{self.experts_held}, {self.first_expert})")
        sizes = (self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                 self.ssm_conv, self.ssm_chunk)
        if any(m not in MIXERS for m in self.layer_mixers) or (
                "ssm" in self.layer_mixers) != all(n > 0 for n in sizes) \
                or ("ssm" not in self.layer_mixers and any(sizes)) \
                or self.ssm_conv == 1 or self.ssm_groups < 1 or (
                    self.ssm_groups > 1 and (
                        "ssm" not in self.layer_mixers
                        or self.ssm_heads % self.ssm_groups)):
            raise ValueError(
                f"layer spec: layer_mixers says one of {MIXERS} of each "
                f"layer of the pattern, and a "
                f"pattern with ssm layers, and nothing else, names "
                f"ssm_heads, ssm_head_dim, ssm_state, ssm_conv >= 2, "
                f"ssm_chunk and may name ssm_groups that divide the heads "
                f"(got {self.layer_mixers}, {sizes}, {self.ssm_groups})")
        if len(set(self.layer_mixers) & set(STATE_MIXERS)) > 1:
            raise ValueError(
                f"layer spec: a pattern's layers with a state are of one "
                f"kind of {tuple(STATE_MIXERS)} (got layer_mixers "
                f"{self.layer_mixers})")
        single = self.residual == "single"
        if ("none" in self.layer_mixers) != single or (
                single and not any(m != "none" for m in self.layer_mixers)):
            raise ValueError(
                f"layer spec: the \"single\" residual, and nothing else, "
                f"has layers that are their FFN alone (\"none\" in "
                f"layer_mixers) beside layers that are their mixer alone "
                f"(got a {self.residual!r} residual with layer_mixers "
                f"{self.layer_mixers})")
        sizes = (self.gdn_key_heads, self.gdn_value_heads, self.gdn_key_dim,
                 self.gdn_value_dim, self.gdn_conv, self.gdn_chunk)
        if ("gdn" in self.layer_mixers) != all(n > 0 for n in sizes) \
                or ("gdn" not in self.layer_mixers and any(sizes)) \
                or self.gdn_conv == 1 or (
                    self.gdn_key_heads
                    and self.gdn_value_heads % self.gdn_key_heads):
            raise ValueError(
                f"layer spec: a pattern with gdn layers, and nothing else, "
                f"names gdn_key_heads, gdn_value_heads (a multiple of "
                f"them), gdn_key_dim, gdn_value_dim, gdn_conv >= 2 and "
                f"gdn_chunk (got {self.layer_mixers}, {sizes})")
        sizes = (self.conv_taps, self.conv_channels)
        if ("conv" in self.layer_mixers) != all(n > 0 for n in sizes) \
                or ("conv" not in self.layer_mixers and any(sizes)) \
                or self.conv_taps == 1:
            raise ValueError(
                f"layer spec: a pattern with conv layers, and nothing "
                f"else, names conv_taps >= 2 and the conv_channels they "
                f"run over (got {self.layer_mixers}, {sizes})")
        if (self.attn_gate or self.qk_norm or self.rotary_dim
                or self.rope_halves) and self.attention != "grouped" \
                or self.rotary_dim < 0 or self.rotary_dim % 2:
            raise ValueError(
                f"layer spec: attn_gate, qk_norm, an even rotary_dim and "
                f"rope_halves describe grouped attention (got "
                f"{self.attn_gate}, {self.qk_norm}, {self.rotary_dim}, "
                f"{self.rope_halves} with {self.attention!r} attention)")
        if min(self.embed_scale, self.residual_scale,
               self.logit_divisor) <= 0 or self.attn_scale < 0 or (
                self.attn_scale and self.attention != "grouped"):
            raise ValueError(
                f"layer spec: embed_scale, residual_scale and "
                f"logit_divisor are positive, and attn_scale (0: "
                f"head_dim ** -0.5) is grouped attention's (got "
                f"{self.embed_scale}, {self.residual_scale}, "
                f"{self.logit_divisor}, {self.attn_scale} with "
                f"{self.attention!r} attention)")
        return self


class StateMixer(NamedTuple):
    """One kind of mixer whose layers keep arrays BY SLOT and no cache
    rows.  `keeps(spec)` is what ONE slot keeps for one such layer,
    ((shape, dtype or None: the cache's), ...); `chunk(spec)` the
    positions its scan takes at once (0: it has no scan); `mix` names the
    function that mixes, `module:function` of this package,
    `mix(spec, p, h, *kept, n_valid, live=None) -> (out, *kept)`, loaded
    when a block of the kind is first built (`mix_fn`; its parameters
    lie in a block's tree under the kind's name); `counters` is the
    family its counters go by (`<counters>.state_bytes`, `.slots_live`,
    `.state_resets`); `step_kernel(spec, kept)` answers (registry op,
    info) of the kernel that walks a decode step's live slots, where
    the kind has one — the engine asks the registry once whether a slot
    that is not running costs the step its first array.  The scopes are
    `<kind>.step` and `<kind>.scan`, under the `state` stage."""

    keeps: object
    chunk: object
    mix: str
    counters: str
    step_kernel: object = None

    def mix_fn(self):
        import importlib

        module, name = self.mix.split(":")
        return getattr(importlib.import_module("." + module, __package__),
                       name)


def _ssm_step_kernel(spec, kept):
    from ..kernels.ssm import ssm_step_info

    return "ssm_step", ssm_step_info(kept[0], spec.ssm_groups)


def _gdn_step_kernel(spec, kept):
    from ..kernels.gdn import gdn_step_info

    return "gdn_step", gdn_step_info(kept[0])


STATE_MIXERS = {
    "ssm": StateMixer(
        keeps=lambda s: (((s.ssm_heads, s.ssm_head_dim, s.ssm_state),
                          "float32"),
                         ((s.ssm_conv - 1, s.ssm_conv_width), None)),
        chunk=lambda s: s.ssm_chunk,
        mix="granite_hybrid:ssm_mix", counters="serve.ssm",
        step_kernel=_ssm_step_kernel),
    "gdn": StateMixer(
        keeps=lambda s: (((s.gdn_value_heads, s.gdn_key_dim,
                           s.gdn_value_dim), "float32"),
                         ((s.gdn_conv - 1, s.gdn_conv_width), None)),
        chunk=lambda s: s.gdn_chunk,
        mix="qwen3_next:gdn_mix", counters="serve.gdn",
        step_kernel=_gdn_step_kernel),
    "conv": StateMixer(
        keeps=lambda s: (((s.conv_taps - 1, s.conv_channels), None),),
        chunk=lambda s: 0,
        mix="lfm2_moe:conv_mix", counters="serve.conv"),
}
