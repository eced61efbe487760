"""The LFM2-MoE family (`model_type` `lfm2_moe`: gated short-convolution
mixers that keep two rows a slot and nothing else, beside a few
grouped-attention layers with normed and rotated q and k, over
sigmoid-routed SiLU-gated experts of which this chip holds a share,
behind two dense layers): how a configuration file becomes the
program's model object, and the arithmetic of what a serving step has to
move and compute.  The arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    types = config["layer_types"]
    return {"d_model": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["assumed"]["head_dim"],
            "taps": config["conv_L_cache"],
            "d_ffn": config["intermediate_size"],
            "d_expert": config["moe_intermediate_size"],
            "experts": config["published"]["num_experts"],
            "held": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "layers": len(types),
            "conv_layers": types.count("conv"),
            "full_layers": types.count("full_attention"),
            "dense_layers": config["num_dense_layers"],
            "routed_layers": len(types) - config["num_dense_layers"],
            "vocab": config["vocab_size"]}


# what the program builds of the family; anything else is refused by name
BUILT = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object
    (`deepspeed_tpu.models.lfm2_moe.Lfm2Moe`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig

    w = widths(config)
    if seq_len > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq_len} exceeds "
                         f"max_position_embeddings")
    if n_dev != 1:
        raise ValueError("one chip's share of a layer is served on one "
                         "chip: the exchange between the chips that share "
                         "a layer is not built")
    for key, built in BUILT.items():
        if config[key] != built:
            raise ValueError(f"{key} = {config[key]!r} is not built "
                             f"(only {built!r})")
    rope = config["rope_parameters"]
    if rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not built "
                         f"(only 'default': no scaling)")
    if w["layers"] != config["num_hidden_layers"]:
        raise ValueError(f"layer_types names {w['layers']} layers, "
                         f"num_hidden_layers says "
                         f"{config['num_hidden_layers']}")
    assumed = config["assumed"]
    if not assumed["tie_word_embeddings"]:
        raise ValueError("an untied head is not built for this family")
    init = assumed["init"]
    return Lfm2Moe(Lfm2MoeConfig(
        vocab_size=w["vocab"], max_seq_len=seq_len,
        layer_types=tuple(config["layer_types"]), d_model=w["d_model"],
        d_ffn=w["d_ffn"], dense_layers=w["dense_layers"],
        num_heads=w["heads"], kv_heads=w["kv_heads"],
        head_dim=w["head_dim"], rope_theta=float(rope["rope_theta"]),
        conv_taps=w["taps"], d_expert=w["d_expert"],
        num_experts=w["experts"], top_k=w["top_k"],
        route_scale=float(config["routed_scaling_factor"]),
        renorm_eps=assumed["renorm_eps"], experts_held=w["held"],
        first_expert=config["held"]["first_expert"],
        norm_eps=config["norm_eps"], init_std=init["std"],
        qk_scale=init["qk_scale"], bias_std=init["bias_std"],
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the rows of the vocabulary held."""
    return config["vocab_size"]


def conv_params(config: dict) -> int:
    """One gated short-convolution mixer: W_in [D, 3 D], the taps (no
    bias) and W_out."""
    w = widths(config)
    d = w["d_model"]
    return 3 * d * d + d * w["taps"] + d * d


def attention_params(config: dict) -> int:
    """One attention mixer: W_q, W_k, W_v, W_o and the two gains of a
    head."""
    w = widths(config)
    d, dh = w["d_model"], w["head_dim"]
    return d * dh * (2 * w["heads"] + 2 * w["kv_heads"]) + 2 * dh


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down — three matrices."""
    w = widths(config)
    return 3 * w["d_model"] * w["d_expert"]


def fixed_params(config: dict) -> int:
    """Parameters that multiply every token whatever the router says,
    over all layers: the mixers, two norms a layer, the dense FFNs, every
    routed layer's router and choosing bias, the final norm and the
    slice of the tied head once.  The embedding is looked up (it IS the
    head's slice: one array)."""
    w = widths(config)
    d = w["d_model"]
    return w["conv_layers"] * conv_params(config) + \
        w["full_layers"] * attention_params(config) + \
        w["layers"] * 2 * d + \
        w["dense_layers"] * 3 * d * w["d_ffn"] + \
        w["routed_layers"] * (d * w["experts"] + w["experts"]) + \
        d + d * w["vocab"]


def held_params(config: dict) -> int:
    """Every parameter held: the fixed ones (the tied slice counted
    once) and the held routed experts."""
    w = widths(config)
    return fixed_params(config) + \
        w["routed_layers"] * w["held"] * expert_params(config)


def active_params(config: dict) -> float:
    """Parameters that multiply one token HERE, on average: the fixed
    ones and, in every routed layer, the held share of its top_k."""
    w = widths(config)
    return fixed_params(config) + w["routed_layers"] * w["top_k"] * \
        w["held"] / w["experts"] * expert_params(config)


def state_bytes(config: dict, itemsize: int = 2) -> int:
    """What ONE convolution layer keeps for ONE request: the last
    `taps - 1` gated inputs of `d_model` at the cache's dtype."""
    w = widths(config)
    return (w["taps"] - 1) * w["d_model"] * itemsize


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One token's cache row in ONE attention layer: `kv_heads` keys and
    as many values."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def conv_flops_per_token(config: dict) -> float:
    """Operations of ONE convolution layer for one token beyond its
    products with the weights: the two gates and a multiply-add a tap,
    over `d_model` channels."""
    w = widths(config)
    return (2.0 + 2.0 * w["taps"]) * w["d_model"]


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE here for one
    trained token: 6 per parameter that multiplies it, attention's two
    products over the rows a causal query attends on average (half the
    sequence) in the attention layers, and three times the
    convolutions'."""
    w = widths(config)
    return 6.0 * active_params(config) + \
        12.0 * w["heads"] * w["head_dim"] * w["full_layers"] * seq_len / 2 \
        + 3.0 * w["conv_layers"] * conv_flops_per_token(config)


def decode_step_cost(config: dict, rows_read: float, batch: float,
                     experts_touched: float, itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` live
    sequences whose queries together attend `rows_read` rows, summed over
    the attention layers, and whose tokens choose `experts_touched`
    different experts among those held in a routed layer, on average: the
    weights outside the routed experts and the head's slice once, each
    touched held expert's THREE matrices once, EACH LIVE sequence's two
    kept rows read once and written once in every convolution layer, the
    rows attended once and one new row a sequence an attention layer;
    products with the weights a token meets here, the convolutions, and
    a score and a weighted sum of `head_dim` for every query head over
    every row read.  Whatever implements the step has to do this much —
    a program that streams the rows of slots that are not live, or
    experts no token chose, does more."""
    w = widths(config)
    flops = batch * (2.0 * active_params(config)
                     + w["conv_layers"] * conv_flops_per_token(config)) \
        + 4.0 * w["heads"] * w["head_dim"] * rows_read
    nbytes = (fixed_params(config)
              + w["routed_layers"] * experts_touched
              * expert_params(config)) * itemsize \
        + 2.0 * batch * w["conv_layers"] * state_bytes(config, itemsize) \
        + (rows_read + batch * w["full_layers"]) * row_bytes(config, itemsize)
    return flops, nbytes
