"""The Nemotron-H family (`model_type` `nemotron_h`: 52 layers that are
each ONE part — a Mamba-2 mixer of 8 groups, grouped attention without
positions, or sigmoid-routed two-matrix relu2 experts beside a shared
one, of which this chip holds a share): how a configuration file becomes
the program's model object, and the arithmetic of what a serving step
has to move and compute.  The arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    pattern = config["hybrid_override_pattern"]
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    return {"d_model": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "ssm_heads": heads, "ssm_head_dim": p, "state": state,
            "groups": groups, "d_inner": heads * p,
            "conv": heads * p + 2 * groups * state,
            "taps": config["conv_kernel"],
            "d_expert": config["moe_intermediate_size"],
            "d_shared": config["moe_shared_expert_intermediate_size"]
            * config["n_shared_experts"],
            "experts": config["published"]["n_routed_experts"],
            "held": config["n_routed_experts"],
            "top_k": config["num_experts_per_tok"],
            "pattern": pattern, "layers": len(pattern),
            "mixer_layers": pattern.count("M"),
            "full_layers": pattern.count("*"),
            "expert_layers": pattern.count("E"),
            "vocab": config["vocab_size"]}


# what the program builds of the family; anything else is refused by name
BUILT = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
         "attention_bias": False, "mamba_proj_bias": False,
         "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
         "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
         "n_shared_experts": 1, "tie_word_embeddings": False,
         "sliding_window": None}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object
    (`deepspeed_tpu.models.nemotron_h.NemotronH`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig

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
    if w["layers"] != config["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern spells {w['layers']} "
                         f"layers, num_hidden_layers says "
                         f"{config['num_hidden_layers']}")
    init = config["assumed"]["init"]
    return NemotronH(NemotronHConfig(
        vocab_size=w["vocab"], max_seq_len=seq_len, pattern=w["pattern"],
        d_model=w["d_model"], num_heads=w["heads"], kv_heads=w["kv_heads"],
        head_dim=w["head_dim"], ssm_heads=w["ssm_heads"],
        ssm_head_dim=w["ssm_head_dim"], ssm_state=w["state"],
        ssm_groups=w["groups"], ssm_conv=w["taps"],
        ssm_chunk=config["chunk_size"], d_expert=w["d_expert"],
        d_shared=w["d_shared"], num_experts=w["experts"],
        top_k=w["top_k"], route_scale=float(config["routed_scaling_factor"]),
        experts_held=w["held"], first_expert=config["held"]["first_expert"],
        norm_eps=config["layer_norm_epsilon"], init_std=init["std"],
        bias_std=init["bias_std"], init_conv=init["conv"],
        init_a=tuple(init["A"]), init_dt=tuple(init["dt"]),
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the rows of the vocabulary held."""
    return config["vocab_size"]


def mixer_params(config: dict) -> int:
    """One Mamba-2 layer: W_in, the taps and their bias, A_log, D and
    dt_bias, the gated norm's gain, W_out and the layer's norm."""
    w = widths(config)
    return w["d_model"] * (w["d_inner"] + w["conv"] + w["ssm_heads"]) + \
        w["conv"] * (w["taps"] + 1) + 3 * w["ssm_heads"] + w["d_inner"] + \
        w["d_inner"] * w["d_model"] + w["d_model"]


def attention_params(config: dict) -> int:
    """One attention layer: W_q, W_k, W_v, W_o and the layer's norm."""
    w = widths(config)
    d, dh = w["d_model"], w["head_dim"]
    return d * dh * (2 * w["heads"] + 2 * w["kv_heads"]) + d


def expert_params(config: dict) -> int:
    """One routed expert: up and down — TWO matrices."""
    w = widths(config)
    return 2 * w["d_model"] * w["d_expert"]


def expert_layer_fixed_params(config: dict) -> int:
    """An expert layer outside its routed experts: the router and its
    choosing bias, the shared expert (two matrices) and the layer's
    norm."""
    w = widths(config)
    return w["d_model"] * w["experts"] + w["experts"] + \
        2 * w["d_model"] * w["d_shared"] + w["d_model"]


def fixed_params(config: dict) -> int:
    """Parameters that multiply every token whatever the router says,
    over all layers, with the final norm and the slice of the output
    head once.  The embedding is looked up."""
    w = widths(config)
    return w["mixer_layers"] * mixer_params(config) + \
        w["full_layers"] * attention_params(config) + \
        w["expert_layers"] * expert_layer_fixed_params(config) + \
        w["d_model"] + w["d_model"] * w["vocab"]


def held_params(config: dict) -> int:
    """Every parameter held: the fixed ones, the held routed experts and
    the slice of the embedding."""
    w = widths(config)
    return fixed_params(config) + w["d_model"] * w["vocab"] + \
        w["expert_layers"] * w["held"] * expert_params(config)


def active_params(config: dict) -> float:
    """Parameters that multiply one token HERE, on average: the fixed
    ones and, in every expert layer, the held share of its top_k."""
    w = widths(config)
    return fixed_params(config) + w["expert_layers"] * w["top_k"] * \
        w["held"] / w["experts"] * expert_params(config)


def state_bytes(config: dict, itemsize: int = 2) -> int:
    """What ONE Mamba-2 layer keeps for ONE request: the float32 state
    `[heads, head_dim, state]` and the convolution's last `taps - 1`
    inputs at the cache's dtype."""
    w = widths(config)
    return w["ssm_heads"] * w["ssm_head_dim"] * w["state"] * 4 + \
        (w["taps"] - 1) * w["conv"] * itemsize


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One token's cache row in ONE attention layer: `kv_heads` keys and
    as many values."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def scan_flops_per_token(config: dict, chunk: int) -> float:
    """Operations of ONE Mamba-2 layer's recurrence for one token,
    beyond its products with the weights: as the recurrence (chunk 1)
    the decay, the rank-one update and the read, 5 over `heads x
    head_dim x state`; as the chunked form, per head the chunk's scores
    row (shared by a group's heads), the decayed sum over the chunk and
    the two products with the state."""
    w = widths(config)
    cell = w["ssm_heads"] * w["ssm_head_dim"] * w["state"]
    if chunk <= 1:
        return 5.0 * cell
    return 2.0 * chunk * w["groups"] * w["state"] + \
        2.0 * chunk * w["d_inner"] + 4.0 * cell


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE here for one
    trained token: 6 per parameter that multiplies it, attention's two
    products over the rows a causal query attends on average (half the
    sequence) in the attention layers, and three times the chunked
    scan's."""
    w = widths(config)
    return 6.0 * active_params(config) + \
        12.0 * w["heads"] * w["head_dim"] * w["full_layers"] * seq_len / 2 \
        + 3.0 * w["mixer_layers"] * scan_flops_per_token(
            config, config["chunk_size"])


def decode_step_cost(config: dict, rows_read: float, batch: float,
                     experts_touched: float, itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` live
    sequences whose queries together attend `rows_read` rows, summed over
    the attention layers, and whose tokens choose `experts_touched`
    different experts among those held in an expert layer, on average:
    the weights outside the routed experts and the head's slice once,
    each touched held expert's TWO matrices once, EACH LIVE sequence's
    state read once and written once in every Mamba-2 layer, the rows
    attended once and one new row a sequence an attention layer;
    products with the weights a token meets here, the recurrence, and a
    score and a weighted sum of `head_dim` for every query head over
    every row read.  Whatever implements the step has to do this much —
    a program that streams the state of slots that are not live, or
    experts no token chose, does more."""
    w = widths(config)
    flops = batch * (2.0 * active_params(config)
                     + w["mixer_layers"] * scan_flops_per_token(config, 1)) \
        + 4.0 * w["heads"] * w["head_dim"] * rows_read
    nbytes = (fixed_params(config)
              + w["expert_layers"] * experts_touched
              * expert_params(config)) * itemsize \
        + 2.0 * batch * w["mixer_layers"] * state_bytes(config, itemsize) \
        + (rows_read + batch * w["full_layers"]) * row_bytes(config, itemsize)
    return flops, nbytes


def prefill_chunk_cost(config: dict, chunk: int, rows_read: float,
                       itemsize: int = 2):
    """(operations, bytes) one prefill chunk of `chunk` tokens needs when
    each of its queries attends `rows_read` rows, summed over the
    attention layers, on average: every weight held once (a chunk's
    tokens reach every held expert), the request's state read and
    written once a Mamba-2 layer, the chunk's rows written and the rows
    its last query attends read once; products with the weights a token
    meets here, the chunked scan, scores and weighted sums over the rows
    read."""
    w = widths(config)
    scan = scan_flops_per_token(config, min(chunk, config["chunk_size"]))
    flops = chunk * (2.0 * active_params(config)
                     + w["mixer_layers"] * scan) + \
        4.0 * w["heads"] * w["head_dim"] * chunk * rows_read
    nbytes = (held_params(config) - w["d_model"] * w["vocab"]) * itemsize + \
        2.0 * w["mixer_layers"] * state_bytes(config, itemsize) + \
        (rows_read / max(w["full_layers"], 1) + chunk) * w["full_layers"] \
        * row_bytes(config, itemsize)
    return flops, nbytes


def ssm_step_cost(config: dict, batch: float):
    """(operations, bytes) the grouped recurrence of ONE decode step
    needs in ONE layer for `batch` live sequences: each one's float32
    state read once and written once, 5 operations a state value."""
    w = widths(config)
    cell = w["ssm_heads"] * w["ssm_head_dim"] * w["state"]
    return 5.0 * batch * cell, 8.0 * batch * cell


def touched_experts_cost(config: dict, batch: float, experts_touched: float,
                         itemsize: int = 2):
    """(operations, bytes) the two-matrix touched-experts product of ONE
    decode step needs in ONE layer: each touched held expert's up and
    down once, and their products for every one of the `batch` rows (the
    walk multiplies every row with every touched expert)."""
    w = widths(config)
    return 2.0 * batch * experts_touched * expert_params(config), \
        experts_touched * expert_params(config) * itemsize


def grouped_experts_cost(config: dict, rows: float, itemsize: int = 2):
    """(operations, bytes) the two-matrix slab product of ONE prefill
    chunk needs in ONE layer for `rows` assignment rows held: every held
    expert's up and down once, the rows' products, the slab in and its
    float32 result out."""
    w = widths(config)
    return 2.0 * rows * expert_params(config), \
        w["held"] * expert_params(config) * itemsize + \
        rows * w["d_model"] * (itemsize + 4)
