"""The DeepSeek-V2 family (`model_type` `deepseek_v2`: latent attention,
routed experts beside shared ones): how a configuration file becomes the
program's model object, and the arithmetic of what a serving step has to
move and compute.  The arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return {"d_model": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "nope": nope, "rope": rope, "v": config["v_head_dim"],
            "rank": config["kv_lora_rank"], "row": config["kv_lora_rank"] + rope,
            "d_ff": config["intermediate_size"],
            "d_expert": config["moe_intermediate_size"],
            "experts": config["n_routed_experts"],
            "top_k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "layers": config["num_hidden_layers"],
            "dense_layers": config["first_k_dense_replace"],
            "vocab": config["vocab_size"]}


# what the program builds of the family; anything else is refused by name
BUILT = {"q_lora_rank": None, "attention_bias": False, "hidden_act": "silu",
         "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
         "topk_method": "greedy", "scoring_func": "softmax",
         "norm_topk_prob": False, "routed_scaling_factor": 1,
         "tie_word_embeddings": False}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object (`deepspeed_tpu.models.DeepSeekV2`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import DeepSeekV2, DeepSeekV2Config

    w = widths(config)
    if seq_len > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq_len} exceeds "
                         f"max_position_embeddings")
    if config["num_key_value_heads"] != w["heads"]:
        raise ValueError("latent attention has full heads")
    if n_dev != 1:
        raise ValueError("one pipeline stage is served on one chip: an "
                         "expert layer that holds a share of the experts "
                         "is not built")
    for key, built in BUILT.items():
        if config[key] != built:
            raise ValueError(f"{key} = {config[key]!r} is not built "
                             f"(only {built!r})")
    yarn = dict(config["rope_scaling"])
    if yarn.pop("type") != "yarn":
        raise ValueError("rope_scaling: only yarn is built")
    init = config["assumed"]["init"]
    return DeepSeekV2(DeepSeekV2Config(
        vocab_size=w["vocab"], max_seq_len=seq_len, num_layers=w["layers"],
        num_heads=w["heads"], d_model=w["d_model"], kv_lora_rank=w["rank"],
        qk_nope_head_dim=w["nope"], qk_rope_head_dim=w["rope"],
        v_head_dim=w["v"], d_ff=w["d_ff"], first_k_dense=w["dense_layers"],
        num_experts=w["experts"], top_k=w["top_k"],
        num_shared_experts=w["shared"], d_expert=w["d_expert"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]), yarn=yarn,
        init_std=init["std"], router_std=init["router_std"],
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the published vocabulary, whole."""
    return config["vocab_size"]


def attention_params(config: dict) -> int:
    """W_q, W_kv_a, W_kv_b and W_o of one layer."""
    w = widths(config)
    d, h = w["d_model"], w["heads"]
    return d * h * (w["nope"] + w["rope"]) + d * w["row"] + \
        w["rank"] * h * (w["nope"] + w["v"]) + h * w["v"] * d


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    w = widths(config)
    return 3 * w["d_model"] * w["d_expert"]


def fixed_params(config: dict) -> int:
    """Parameters that multiply every token whatever the router says:
    attention in every layer, the leading dense FFNs, the router and the
    shared experts of every routed layer, and the output head once.  The
    embedding is looked up; norms are vectors."""
    w = widths(config)
    d, routed = w["d_model"], w["layers"] - w["dense_layers"]
    return w["layers"] * attention_params(config) + \
        w["dense_layers"] * 3 * d * w["d_ff"] + \
        routed * (d * w["experts"] + w["shared"] * expert_params(config)) + \
        d * w["vocab"]


def held_params(config: dict) -> int:
    """Every parameter held: the fixed ones, all the routed experts and
    the embedding."""
    w = widths(config)
    return fixed_params(config) + w["d_model"] * w["vocab"] + \
        (w["layers"] - w["dense_layers"]) * w["experts"] * \
        expert_params(config)


def active_params(config: dict) -> int:
    """Parameters that multiply one token: the fixed ones and the top_k
    experts it chooses in every routed layer."""
    w = widths(config)
    return fixed_params(config) + (w["layers"] - w["dense_layers"]) * \
        w["top_k"] * expert_params(config)


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE for one trained
    token: 6 per parameter that multiplies it, plus attention's two S x S
    products over per-head keys of nope + rope and values of v (forward
    and backward 3 x 2 x S x H x (nope + rope + v) a layer), halved
    because half of a causal score matrix is never needed."""
    w = widths(config)
    attn = 6 * w["layers"] * seq_len * w["heads"] * \
        (w["nope"] + w["rope"] + w["v"])
    return 6.0 * active_params(config) + attn / 2


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One token's latent rows in all the layers held here: [c | k_r],
    one row a layer for all heads."""
    w = widths(config)
    return w["layers"] * w["row"] * itemsize


def decode_step_cost(config: dict, rows_read: float, batch: float,
                     experts_touched: float, itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` sequences
    that together attend `rows_read` latent rows and whose tokens choose
    `experts_touched` different experts in a routed layer, on average:
    the fixed weights once, each touched expert's weights once, the rows
    read once and one new row a sequence; products with the weights a
    token meets, and the absorbed path's scores and weighted sums over
    the rows read (rank + rope and rank wide, every head) with its two
    products through W_kv_b's halves a query.  Whatever implements the
    step has to do this much."""
    w = widths(config)
    routed = w["layers"] - w["dense_layers"]
    h = w["heads"]
    flops = 2.0 * active_params(config) * batch + \
        2.0 * w["layers"] * h * (rows_read * (2 * w["rank"] + w["rope"])
                                 + batch * w["rank"] * (w["nope"] + w["v"]))
    nbytes = (fixed_params(config)
              + routed * experts_touched * expert_params(config)) * itemsize \
        + (rows_read + batch) * row_bytes(config, itemsize)
    return flops, nbytes


def prefill_chunk_cost(config: dict, chunk: int, rows_read: float,
                       itemsize: int = 2):
    """(operations, bytes) one prefill chunk of `chunk` tokens needs when
    its queries attend `rows_read` rows each on average: the fixed
    weights once and every expert the chunk's `chunk x top_k`
    assignments can touch, the chunk's rows written, the rows it attends
    read once; products with the weights a token meets, the expansion of
    the attended rows through W_kv_b, and scores and weighted sums over
    per-head keys and values."""
    w = widths(config)
    routed = w["layers"] - w["dense_layers"]
    h = w["heads"]
    held = rows_read + chunk / 2.0     # rows the last query attends
    touched = min(w["experts"], chunk * w["top_k"])
    flops = 2.0 * active_params(config) * chunk + 2.0 * w["layers"] * h * (
        held * w["rank"] * (w["nope"] + w["v"])
        + chunk * rows_read * (w["nope"] + w["rope"] + w["v"]))
    nbytes = (fixed_params(config)
              + routed * touched * expert_params(config)) * itemsize + \
        (held + chunk) * row_bytes(config, itemsize)
    return flops, nbytes
