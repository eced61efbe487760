"""The GLM-5.2 family (`model_type` `glm_moe_dsa`: latent attention with
a query low-rank path, a learned selection of the rows a query attends
that some layers compute and the others share, sigmoid-routed experts
under a selection bias beside one shared expert, of which this chip
holds a share): how a configuration file becomes the program's model
object, and the arithmetic of what a serving step has to move and
compute.  The arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    layers = config["held"]["layers"]
    kinds = [config["indexer_types"][i] for i in layers]
    return {"d_model": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "nope": nope, "rope": rope, "v": config["v_head_dim"],
            "q_rank": config["q_lora_rank"],
            "rank": config["kv_lora_rank"],
            "row": config["kv_lora_rank"] + rope,
            "index_heads": config["index_n_heads"],
            "index_dim": config["index_head_dim"],
            "topk": config["index_topk"],
            "d_ff": config["intermediate_size"],
            "d_expert": config["moe_intermediate_size"],
            "experts": config["published"]["n_routed_experts"],
            "held": config["n_routed_experts"],
            "top_k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "layers": config["num_hidden_layers"],
            "full_layers": kinds.count("full"),
            "indexer": tuple(kinds),
            "dense_layers": config["first_k_dense_replace"],
            "vocab": config["vocab_size"]}


# what the program builds of the family; anything else is refused by name
BUILT = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
         "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
         "scoring_func": "sigmoid", "norm_topk_prob": True,
         "rope_interleave": True, "indexer_rope_interleave": True,
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object
    (`deepspeed_tpu.models.glm_moe_dsa.GlmMoeDsa`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.glm_moe_dsa import GlmMoeDsa, GlmMoeDsaConfig

    w = widths(config)
    if seq_len > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq_len} exceeds "
                         f"max_position_embeddings")
    if config["num_key_value_heads"] != w["heads"] or \
            config["qk_head_dim"] != w["nope"] + w["rope"]:
        raise ValueError("latent attention has full heads of "
                         "qk_nope_head_dim + qk_rope_head_dim")
    if n_dev != 1:
        raise ValueError("one chip's share of a layer is served on one "
                         "chip: the exchange between the chips that share "
                         "a layer is not built")
    for key, built in BUILT.items():
        if config[key] != built:
            raise ValueError(f"{key} = {config[key]!r} is not built "
                             f"(only {built!r})")
    if config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("rope_parameters: only the default type is built")
    kinds = [config["mlp_layer_types"][i] for i in config["held"]["layers"]]
    if kinds != ["dense"] * w["dense_layers"] + \
            ["sparse"] * (w["layers"] - w["dense_layers"]) or \
            len(kinds) != w["layers"]:
        raise ValueError(f"the layers held ({config['held']['layers']}) "
                         f"are {kinds}: not first_k_dense_replace dense "
                         f"layers in front of sparse ones")
    init = config["assumed"]["init"]
    return GlmMoeDsa(GlmMoeDsaConfig(
        vocab_size=w["vocab"], max_seq_len=seq_len, num_layers=w["layers"],
        num_heads=w["heads"], d_model=w["d_model"], q_lora_rank=w["q_rank"],
        kv_lora_rank=w["rank"], qk_nope_head_dim=w["nope"],
        qk_rope_head_dim=w["rope"], v_head_dim=w["v"],
        index_heads=w["index_heads"], index_head_dim=w["index_dim"],
        index_topk=w["topk"],
        index_norm_eps=config["assumed"]["index_norm_eps"],
        indexer_types=w["indexer"], d_ff=w["d_ff"],
        first_k_dense=w["dense_layers"], num_experts=w["experts"],
        top_k=w["top_k"], num_shared_experts=w["shared"],
        d_expert=w["d_expert"],
        route_scale=float(config["routed_scaling_factor"]),
        experts_held=w["held"], first_expert=config["held"]["first_expert"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        init_std=init["std"], router_std=init["router_std"],
        bias_std=init["bias_std"], query_std=init["query_std"],
        embed_std=init["embed_std"],
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the rows of the vocabulary held."""
    return config["vocab_size"]


def attention_params(config: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one layer."""
    w = widths(config)
    d, h = w["d_model"], w["heads"]
    return d * w["q_rank"] + w["q_rank"] * h * (w["nope"] + w["rope"]) + \
        d * w["row"] + w["rank"] * h * (w["nope"] + w["v"]) + h * w["v"] * d


def indexer_params(config: dict) -> int:
    """W^I_q, W^I_k and W^I_w of one "full" layer."""
    w = widths(config)
    return w["q_rank"] * w["index_heads"] * w["index_dim"] + \
        w["d_model"] * (w["index_dim"] + w["index_heads"])


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    w = widths(config)
    return 3 * w["d_model"] * w["d_expert"]


def fixed_params(config: dict) -> int:
    """Parameters that multiply every token whatever the router says:
    attention in every layer, the indexer in the "full" ones, the
    leading dense FFN, the router and the shared expert of every routed
    layer, and the slice of the output head once.  The embedding is
    looked up; norms and the selection bias are vectors."""
    w = widths(config)
    d, routed = w["d_model"], w["layers"] - w["dense_layers"]
    return w["layers"] * attention_params(config) + \
        w["full_layers"] * indexer_params(config) + \
        w["dense_layers"] * 3 * d * w["d_ff"] + \
        routed * (d * w["experts"] + w["shared"] * expert_params(config)) + \
        d * w["vocab"]


def held_params(config: dict) -> int:
    """Every parameter held: the fixed ones, the held routed experts and
    the slice of the embedding."""
    w = widths(config)
    return fixed_params(config) + w["d_model"] * w["vocab"] + \
        (w["layers"] - w["dense_layers"]) * w["held"] * expert_params(config)


def active_params(config: dict) -> float:
    """Parameters that multiply one token HERE, on average: the fixed
    ones and, in every routed layer, the held share of its top_k
    experts."""
    w = widths(config)
    return fixed_params(config) + (w["layers"] - w["dense_layers"]) * \
        w["top_k"] * w["held"] / w["experts"] * expert_params(config)


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE here for one
    trained token: 6 per parameter that multiplies it, plus the index
    scores over the causal half and attention over at most `topk`
    rows."""
    w = widths(config)
    rows = min(seq_len / 2.0, w["topk"])
    return 6.0 * active_params(config) + \
        6.0 * w["full_layers"] * seq_len / 2.0 * w["index_heads"] * \
        w["index_dim"] + 6.0 * w["layers"] * rows * w["heads"] * \
        (w["nope"] + w["rope"] + w["v"])


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One token's rows in all the layers held here: a latent row
    [c | k_r] a layer and an index key in every "full" one."""
    w = widths(config)
    return (w["layers"] * w["row"]
            + w["full_layers"] * w["index_dim"]) * itemsize


def decode_step_cost(config: dict, keys_scored: float, rows_selected: float,
                     batch: float, experts_touched: float, itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` sequences
    whose queries together score `keys_scored` index keys (summed over
    the "full" layers) and attend `rows_selected` latent rows (summed
    over all the layers), and whose tokens choose `experts_touched`
    different experts among those held in a routed layer, on average:
    the fixed weights and the head once, each touched held expert's
    weights once, every index key scored and every chosen latent row
    once, one new row a sequence a layer; products with the weights a
    token meets here, an index score of `index_heads` heads for every
    key, and the absorbed path's scores and weighted sums over the rows
    chosen with its two products through W_kvb's halves a query.  What
    the MATHEMATICS needs — a step that gathers more rows than it chose,
    or scans the keys twice, reads more than this."""
    w = widths(config)
    routed = w["layers"] - w["dense_layers"]
    h = w["heads"]
    flops = 2.0 * active_params(config) * batch + \
        2.0 * keys_scored * w["index_heads"] * w["index_dim"] + \
        2.0 * h * (rows_selected * (2 * w["rank"] + w["rope"])
                   + batch * w["layers"] * w["rank"] * (w["nope"] + w["v"]))
    nbytes = (fixed_params(config)
              + routed * experts_touched * expert_params(config)) * itemsize \
        + (keys_scored * w["index_dim"] + rows_selected * w["row"]) \
        * itemsize + batch * row_bytes(config, itemsize)
    return flops, nbytes
