"""The Qwen3-Next family (`model_type` `qwen3_next`: gated delta-rule
linear-attention layers that keep a matrix state a request beside gated
grouped-attention layers with partial rotary positions, softmax-routed
experts beside one shared expert behind a sigmoid gate, of which this
chip holds a share): how a configuration file becomes the program's model
object, and the arithmetic of what a serving step has to move and
compute.  The arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    period = config["full_attention_interval"]
    layers = config["held"]["layers"]
    full = sum((i + 1) % period == 0 for i in layers)
    return {"d_model": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rotary": int(config["partial_rotary_factor"]
                          * config["head_dim"]),
            "key_heads": hk, "value_heads": hv, "key_dim": dk,
            "value_dim": dv, "key_width": hk * dk, "value_width": hv * dv,
            "conv": 2 * hk * dk + hv * dv,
            "taps": config["linear_conv_kernel_dim"],
            "d_expert": config["moe_intermediate_size"],
            "d_shared": config["shared_expert_intermediate_size"],
            "experts": config["published"]["num_experts"],
            "held": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "period": period, "layers": config["num_hidden_layers"],
            "full_layers": full, "delta_layers": len(layers) - full,
            "vocab": config["vocab_size"]}


# what the program builds of the family; anything else is refused by name
BUILT = {"decoder_sparse_step": 1, "mlp_only_layers": [],
         "hidden_act": "silu", "norm_topk_prob": True, "rope_scaling": None,
         "tie_word_embeddings": False, "use_sliding_window": False}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object
    (`deepspeed_tpu.models.qwen3_next.Qwen3Next`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig

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
    layers = config["held"]["layers"]
    if len(layers) != w["layers"] or layers[0] % w["period"] or \
            layers != list(range(layers[0], layers[0] + len(layers))):
        raise ValueError(f"the layers held ({layers}) are not "
                         f"num_hidden_layers consecutive layers from the "
                         f"start of a period of {w['period']}")
    init = config["assumed"]["init"]
    return Qwen3Next(Qwen3NextConfig(
        vocab_size=w["vocab"], max_seq_len=seq_len, num_layers=w["layers"],
        period=w["period"], d_model=w["d_model"], num_heads=w["heads"],
        kv_heads=w["kv_heads"], head_dim=w["head_dim"],
        rotary_dim=w["rotary"], rope_theta=float(config["rope_theta"]),
        gdn_key_heads=w["key_heads"], gdn_value_heads=w["value_heads"],
        gdn_key_dim=w["key_dim"], gdn_value_dim=w["value_dim"],
        gdn_conv=w["taps"], gdn_chunk=config["assumed"]["scan_chunk"],
        d_expert=w["d_expert"], d_shared=w["d_shared"],
        num_experts=w["experts"], top_k=w["top_k"], experts_held=w["held"],
        first_expert=config["held"]["first_expert"],
        rms_norm_eps=config["rms_norm_eps"], init_std=init["std"],
        router_std=init["router_std"], init_a=tuple(init["A"]),
        init_dt=tuple(init["dt"]),
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the rows of the vocabulary held."""
    return config["vocab_size"]


def mixer_params(config: dict) -> int:
    """One gated delta mixer: W_qkvz, W_ba, the convolution's taps, W_o,
    A_log, dt_bias and the gated norm's gain."""
    w = widths(config)
    return w["d_model"] * (w["conv"] + w["value_width"]) + \
        w["d_model"] * 2 * w["value_heads"] + w["conv"] * w["taps"] + \
        w["value_width"] * w["d_model"] + 2 * w["value_heads"] + \
        w["value_dim"]


def attention_params(config: dict) -> int:
    """W_q ([q | gate] a head), W_k, W_v, W_o and the two head norms."""
    w = widths(config)
    d, dh = w["d_model"], w["head_dim"]
    return d * dh * (3 * w["heads"] + 2 * w["kv_heads"]) + 2 * dh


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    w = widths(config)
    return 3 * w["d_model"] * w["d_expert"]


def shared_params(config: dict) -> int:
    """The shared expert and its gate."""
    w = widths(config)
    return 3 * w["d_model"] * w["d_shared"] + w["d_model"]


def layer_fixed_params(config: dict, full: bool) -> int:
    """What multiplies every token in one layer whatever the router
    says: the mixer, the router, the shared expert and its gate, and the
    layer's two norms."""
    w = widths(config)
    mix = attention_params(config) if full else mixer_params(config)
    return mix + w["d_model"] * w["experts"] + shared_params(config) + \
        2 * w["d_model"]


def fixed_params(config: dict) -> int:
    """Parameters that multiply every token whatever the router says,
    over the layers held, with the final norm and the slice of the output
    head once.  The embedding is looked up."""
    w = widths(config)
    return w["delta_layers"] * layer_fixed_params(config, False) + \
        w["full_layers"] * layer_fixed_params(config, True) + \
        w["d_model"] + w["d_model"] * w["vocab"]


def held_params(config: dict) -> int:
    """Every parameter held: the fixed ones, the held routed experts and
    the slice of the embedding."""
    w = widths(config)
    return fixed_params(config) + w["d_model"] * w["vocab"] + \
        w["layers"] * w["held"] * expert_params(config)


def active_params(config: dict) -> float:
    """Parameters that multiply one token HERE, on average: the fixed
    ones and, in every layer, the held share of its top_k experts."""
    w = widths(config)
    return fixed_params(config) + w["layers"] * w["top_k"] * \
        w["held"] / w["experts"] * expert_params(config)


def state_bytes(config: dict, itemsize: int = 2) -> int:
    """What ONE delta layer keeps for ONE request: the float32 state
    `[value_heads, key_dim, value_dim]` and the convolution's last
    `taps - 1` inputs at the cache's dtype."""
    w = widths(config)
    return w["value_heads"] * w["key_dim"] * w["value_dim"] * 4 + \
        (w["taps"] - 1) * w["conv"] * itemsize


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One token's cache row in ONE full layer: `kv_heads` keys and as
    many values."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def scan_flops_per_token(config: dict, chunk: int) -> float:
    """Operations of ONE delta layer's rule for one token, beyond its
    products with the weights: as the recurrence (chunk 1) the decay,
    S^T k, the rank-one update and S^T q, 7 over `value_heads x key_dim x
    value_dim`; as the chunked form, per head the chunk's K K^T and Q K^T
    rows, the forward substitution's row, the two products through the
    inverse and the three with the state."""
    w = widths(config)
    cell = w["value_heads"] * w["key_dim"] * w["value_dim"]
    if chunk <= 1:
        return 7.0 * cell
    per_head = 2.0 * chunk * (2 * w["key_dim"]) + 2.0 * chunk * chunk / 2 \
        + 2.0 * chunk * (w["key_dim"] + w["value_dim"]) \
        + 2.0 * chunk * w["value_dim"]
    return w["value_heads"] * per_head + 6.0 * cell


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE here for one
    trained token: 6 per parameter that multiplies it, attention's two
    products over the rows a causal query attends on average (half the
    sequence) in the full layers, and three times the chunked rule's."""
    w = widths(config)
    return 6.0 * active_params(config) + \
        12.0 * w["heads"] * w["head_dim"] * w["full_layers"] * seq_len / 2 \
        + 3.0 * w["delta_layers"] * scan_flops_per_token(
            config, config["assumed"]["scan_chunk"])


def decode_step_cost(config: dict, rows_read: float, batch: float,
                     experts_touched: float, itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` live
    sequences whose queries together attend `rows_read` rows, summed over
    the full layers, and whose tokens choose `experts_touched` different
    experts among those held in a layer, on average: the weights outside
    the experts and the head once, each touched held expert's weights
    once, EACH LIVE sequence's state read once and written once in every
    delta layer, the rows attended once and one new row a sequence a
    full layer; products with the weights a token meets here, the
    recurrence, and a score and a weighted sum of `head_dim` for every
    query head over every row read.  Whatever implements the step has to
    do this much — a program that streams the state of slots that are
    not live, or experts no token chose, does more."""
    w = widths(config)
    flops = batch * (2.0 * active_params(config)
                     + w["delta_layers"] * scan_flops_per_token(config, 1)) \
        + 4.0 * w["heads"] * w["head_dim"] * rows_read
    nbytes = (fixed_params(config)
              + w["layers"] * experts_touched * expert_params(config)) \
        * itemsize \
        + 2.0 * batch * w["delta_layers"] * state_bytes(config, itemsize) \
        + (rows_read + batch * w["full_layers"]) * row_bytes(config, itemsize)
    return flops, nbytes


def prefill_chunk_cost(config: dict, chunk: int, rows_read: float,
                       itemsize: int = 2):
    """(operations, bytes) one prefill chunk of `chunk` tokens needs when
    each of its queries attends `rows_read` rows, summed over the full
    layers, on average: every weight held once (a chunk's tokens reach
    every held expert), the request's state read and written once a
    delta layer, the chunk's rows written and the rows its last query
    attends read once; products with the weights a token meets here, the
    chunked rule, scores and weighted sums over the rows read."""
    w = widths(config)
    scan = scan_flops_per_token(
        config, min(chunk, config["assumed"]["scan_chunk"]))
    flops = chunk * (2.0 * active_params(config)
                     + w["delta_layers"] * scan) + \
        4.0 * w["heads"] * w["head_dim"] * chunk * rows_read
    nbytes = (held_params(config) - w["d_model"] * w["vocab"]) * itemsize + \
        2.0 * w["delta_layers"] * state_bytes(config, itemsize) + \
        (rows_read / max(w["full_layers"], 1) + chunk) * w["full_layers"] \
        * row_bytes(config, itemsize)
    return flops, nbytes


def gdn_step_cost(config: dict, batch: float):
    """(operations, bytes) the delta rule of ONE decode step needs in ONE
    layer for `batch` live sequences: each one's float32 state read once
    and written once, 7 operations a state value."""
    w = widths(config)
    cell = w["value_heads"] * w["key_dim"] * w["value_dim"]
    return 7.0 * batch * cell, 8.0 * batch * cell
