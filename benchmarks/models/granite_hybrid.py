"""The Granite 4.0-H family (`model_type` `granitemoehybrid`, dense:
Mamba-2 state-space layers beside a few grouped-attention layers without
positions, a gated MLP in every layer, four scalars on the stream): how a
configuration file becomes the program's model object, and the
arithmetic of what a serving step has to move and compute.  The
arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    return {"d_model": config["hidden_size"],
            "d_ffn": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"]
            // config["num_attention_heads"],
            "ssm_heads": heads, "ssm_head_dim": p,
            "d_inner": heads * p, "state": config["mamba_d_state"],
            "taps": config["mamba_d_conv"],
            "conv": heads * p + 2 * config["mamba_n_groups"]
            * config["mamba_d_state"],
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"]}


# what the program builds of the family; anything else is refused by name
BUILT = {"num_local_experts": 0, "num_experts_per_tok": 0,
         "mamba_n_groups": 1, "mamba_proj_bias": False,
         "mamba_conv_bias": True, "attention_bias": False,
         "position_embedding_type": "nope", "hidden_act": "silu",
         "normalization_function": "rmsnorm", "tie_word_embeddings": True}


def pattern(config: dict):
    """(period, which layers of a period attend) of `layer_types`: the
    shortest pattern that, repeated, gives every layer held."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    if set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types names {sorted(set(kinds))}; built: "
                         f"'mamba' and 'attention'")
    period = next(p for p in range(1, len(kinds) + 1)
                  if all(k == kinds[i % p] for i, k in enumerate(kinds)))
    return period, tuple(i for i in range(period)
                         if kinds[i] == "attention")


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object
    (`deepspeed_tpu.models.GraniteHybrid`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import GraniteHybrid, GraniteHybridConfig

    w = widths(config)
    if seq_len > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq_len} exceeds "
                         f"max_position_embeddings")
    if n_dev != 1:
        raise ValueError("the model is served whole on one chip: the split "
                         "of a state's heads over chips is not built")
    for key, built in BUILT.items():
        if config[key] != built:
            raise ValueError(f"{key} = {config[key]!r} is not built "
                             f"(only {built!r})")
    if w["d_inner"] != config["mamba_expand"] * w["d_model"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    period, attention_at = pattern(config)
    init = config["assumed"]["init"]
    return GraniteHybrid(GraniteHybridConfig(
        vocab_size=w["vocab"], max_seq_len=seq_len, num_layers=w["layers"],
        period=period, attention_at=attention_at, d_model=w["d_model"],
        d_ffn=w["d_ffn"], num_heads=w["heads"], kv_heads=w["kv_heads"],
        head_dim=w["head_dim"], ssm_heads=w["ssm_heads"],
        ssm_head_dim=w["ssm_head_dim"], ssm_state=w["state"],
        ssm_conv=w["taps"], ssm_chunk=config["mamba_chunk_size"],
        rms_norm_eps=config["rms_norm_eps"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        init_std=init["std"], init_a=tuple(init["A"]),
        init_dt=tuple(init["dt"]),
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the whole vocabulary."""
    return config["vocab_size"]


def layer_counts(config: dict):
    """(state-space layers, attention layers) held."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def mixer_params(config: dict) -> int:
    """One Mamba-2 mixer: W_in, the convolution's taps and bias, dt_bias,
    A_log and D, the gated norm's gain, W_out."""
    w = widths(config)
    return w["d_model"] * (w["d_inner"] + w["conv"] + w["ssm_heads"]) + \
        w["conv"] * (w["taps"] + 1) + 3 * w["ssm_heads"] + w["d_inner"] + \
        w["d_inner"] * w["d_model"]


def attention_params(config: dict) -> int:
    """W_q, W_o (the query heads) and W_k, W_v (the K/V heads)."""
    w = widths(config)
    return 2 * w["d_model"] * w["head_dim"] * (w["heads"] + w["kv_heads"])


def mlp_params(config: dict) -> int:
    """Gate, up and down of one layer's MLP, and the layer's two norms."""
    w = widths(config)
    return 3 * w["d_model"] * w["d_ffn"] + 2 * w["d_model"]


def total_params(config: dict) -> int:
    """Every parameter: the layers, the embedding (the head is the
    embedding, counted once) and the final norm."""
    w = widths(config)
    ssm, attn = layer_counts(config)
    return ssm * mixer_params(config) + attn * attention_params(config) + \
        (ssm + attn) * mlp_params(config) + \
        w["vocab"] * w["d_model"] + w["d_model"]


def state_bytes(config: dict, itemsize: int = 2) -> int:
    """What ONE state-space layer keeps for ONE request: the float32
    state `[heads, head_dim, state]` and the convolution's last
    `taps - 1` inputs at the cache's dtype."""
    w = widths(config)
    return w["ssm_heads"] * w["ssm_head_dim"] * w["state"] * 4 + \
        (w["taps"] - 1) * w["conv"] * itemsize


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One token's cache row in ONE attention layer: `kv_heads` keys and
    as many values."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def scan_flops_per_token(config: dict, chunk: int) -> float:
    """Operations of ONE state-space layer's scan for one token, beyond
    its products with the weights: as the recurrence (chunk 1) the
    state's update and its read-out, 5 over `heads x head_dim x state`;
    as the chunked form, scores against the `chunk / 2` positions of its
    chunk before it on average, their weighted sum over the heads'
    values, and the state decayed into the output and the chunk's
    inputs into the state."""
    w = widths(config)
    cell = w["ssm_heads"] * w["ssm_head_dim"] * w["state"]
    if chunk <= 1:
        return 5.0 * cell
    return chunk * (w["state"] + w["d_inner"]) + 4.0 * cell


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE for one trained
    token: 6 per parameter, plus attention's two products over the rows
    a causal query attends on average (half the sequence) in the
    attention layers, plus three times the chunked scan's."""
    w = widths(config)
    ssm, attn = layer_counts(config)
    return 6.0 * total_params(config) + \
        12.0 * w["heads"] * w["head_dim"] * attn * seq_len / 2 + \
        3.0 * ssm * scan_flops_per_token(config, config["mamba_chunk_size"])


def decode_step_cost(config: dict, rows_read: float, batch: float,
                     itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` live
    sequences whose queries together attend `rows_read` rows, summed over
    the attention layers: the weights and the head once, EACH LIVE
    sequence's state read once and written once in every state-space
    layer, the rows read once and one new row a sequence an attention
    layer; products with every weight, the recurrence, and a score and a
    weighted sum of `head_dim` for every query head over every row read.
    Whatever implements the step has to do this much — a program that
    streams the state of slots that are not live does more."""
    w = widths(config)
    ssm, attn = layer_counts(config)
    flops = batch * (2.0 * total_params(config)
                     + ssm * scan_flops_per_token(config, 1)) + \
        4.0 * w["heads"] * w["head_dim"] * rows_read
    nbytes = total_params(config) * itemsize + \
        2.0 * batch * ssm * state_bytes(config, itemsize) + \
        (rows_read + batch * attn) * row_bytes(config, itemsize)
    return flops, nbytes


def prefill_chunk_cost(config: dict, chunk: int, rows_read: float,
                       itemsize: int = 2):
    """(operations, bytes) one prefill chunk of `chunk` tokens needs when
    each of its queries attends `rows_read` rows, summed over the
    attention layers, on average: the weights once, the request's state
    read and written once a state-space layer, the chunk's rows written
    and the rows its last query attends read once; products with every
    weight, the chunked scan, scores and weighted sums over the rows
    read."""
    w = widths(config)
    ssm, attn = layer_counts(config)
    scan = scan_flops_per_token(config,
                                min(chunk, config["mamba_chunk_size"]))
    flops = chunk * (2.0 * total_params(config) + ssm * scan) + \
        4.0 * w["heads"] * w["head_dim"] * chunk * rows_read
    nbytes = total_params(config) * itemsize + \
        2.0 * ssm * state_bytes(config, itemsize) + \
        (rows_read + chunk / 2.0 * attn + chunk * attn) * \
        row_bytes(config, itemsize)
    return flops, nbytes
