"""The Command A+ family (`model_type` `cohere2_moe`: sliding layers with
rotary positions beside full layers without, grouped K/V heads, a parallel
block, sigmoid-routed experts of which a chip holds a share, averaged
shared experts): how a configuration file becomes the program's model
object, and the arithmetic of what a serving step has to move and
compute ON THIS CHIP — its share of the experts and of the vocabulary.
The arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    return {"d_model": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "d_expert": config["intermediate_size"],
            "held": config["num_experts"],
            "experts": config["published"]["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "shared": config["num_shared_experts"],
            "layers": config["num_hidden_layers"],
            "window": config["sliding_window"],
            "period": config["layer_switch"],
            "vocab": config["vocab_size"]}


# what the program builds of the family; anything else is refused by name
BUILT = {"attention_bias": False, "hidden_act": "silu",
         "use_gated_activation": True, "use_qk_norm": False,
         "use_parallel_block": True, "use_parallel_embedding": False,
         "position_embedding_type": "rope_gptj", "rotary_pct": 1,
         "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
         "order_of_interleaved_layers": "local_attn_first",
         "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
         "shared_expert_combination_strategy": "average",
         "first_k_dense_replace": 0, "tie_word_embeddings": True,
         "logit_scale": 1}


def layer_windows(config: dict):
    """The window of each layer held (0: a full layer), from
    `layer_types`."""
    kinds = {"sliding_attention": config["sliding_window"],
             "full_attention": 0}
    return [kinds[t] for t in
            config["layer_types"][:config["num_hidden_layers"]]]


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object (`deepspeed_tpu.models.Cohere2Moe`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import Cohere2Moe, Cohere2MoeConfig

    w = widths(config)
    if seq_len > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq_len} exceeds "
                         f"max_position_embeddings")
    if n_dev != 1:
        raise ValueError("one chip's share of a layer is served on one "
                         "chip: the all-to-all between the chips that "
                         "share a layer is not built")
    for key, built in BUILT.items():
        if config[key] != built:
            raise ValueError(f"{key} = {config[key]!r} is not built "
                             f"(only {built!r})")
    period = w["period"]
    pattern = [w["window"]] * (period - 1) + [0]
    if layer_windows(config) != [pattern[i % period]
                                 for i in range(w["layers"])]:
        raise ValueError(f"layer_types is not {period - 1} sliding layers "
                         f"and a full one, over and over")
    init = config["assumed"]["init"]
    return Cohere2Moe(Cohere2MoeConfig(
        vocab_size=w["vocab"], max_seq_len=seq_len, num_layers=w["layers"],
        num_heads=w["heads"], kv_heads=w["kv_heads"],
        head_dim=w["head_dim"], d_model=w["d_model"],
        d_expert=w["d_expert"], num_experts=w["experts"], top_k=w["top_k"],
        num_shared=w["shared"],
        experts_held=0 if w["held"] == w["experts"] else w["held"],
        first_expert=config["held"]["first_expert"], window=w["window"],
        period=period, layer_norm_eps=config["layer_norm_eps"],
        rope_theta=float(config["rope_theta"]), init_std=init["std"],
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the rows of the vocabulary held."""
    return config["vocab_size"]


def attention_params(config: dict) -> int:
    """W_q, W_o (all the query heads) and W_k, W_v (the K/V heads) of
    one layer."""
    w = widths(config)
    return 2 * w["d_model"] * w["head_dim"] * (w["heads"] + w["kv_heads"])


def expert_params(config: dict) -> int:
    """One routed expert, or one shared one: gate, up and down."""
    w = widths(config)
    return 3 * w["d_model"] * w["d_expert"]


def fixed_params(config: dict) -> int:
    """Parameters that multiply every token whatever the router says:
    attention, the router and the shared experts of every layer, and the
    tied head over the rows of the vocabulary held.  Norms are vectors."""
    w = widths(config)
    return w["layers"] * (attention_params(config)
                          + w["d_model"] * w["experts"]
                          + w["shared"] * expert_params(config)) + \
        w["d_model"] * w["vocab"]


def held_params(config: dict) -> int:
    """Every parameter held here: the fixed ones (the head is the
    embedding, counted once) and the routed experts held."""
    w = widths(config)
    return fixed_params(config) + \
        w["layers"] * w["held"] * expert_params(config)


def active_params(config: dict) -> float:
    """Parameters that multiply one token HERE, on average: the fixed
    ones and, of the `top_k` experts it chooses in every layer, the
    share `held / experts` that this chip holds."""
    w = widths(config)
    return fixed_params(config) + w["layers"] * w["top_k"] * \
        w["held"] / w["experts"] * expert_params(config)


def attended_rows(config: dict, cached: float) -> float:
    """Rows a query with `cached` positions behind and including it
    attends, summed over the layers held: the window's in a sliding
    layer, all of them in a full one."""
    return sum(min(cached, w) if w else cached
               for w in layer_windows(config))


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE of this chip
    for one trained token: 6 per parameter that multiplies it here, plus
    attention's two products over keys and values of `head_dim` for
    every query head (forward and backward 3 x 2 x 2 x H x head_dim a
    row attended), over the rows a causal query attends on average: half
    the sequence in a full layer, at most the window in a sliding one."""
    w = widths(config)
    rows = sum(min(seq_len / 2, wl) if wl else seq_len / 2
               for wl in layer_windows(config))
    return 6.0 * active_params(config) + \
        12.0 * w["heads"] * w["head_dim"] * rows


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One token's cache row in ONE layer: `kv_heads` keys and as many
    values."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def decode_step_cost(config: dict, rows_read: float, batch: float,
                     experts_touched: float, itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` sequences
    whose queries together attend `rows_read` rows, summed over the
    layers, and whose tokens choose `experts_touched` different experts
    among those held in a layer, on average: the fixed weights and the
    head once, each touched expert's weights once, the rows read once
    and one new row a sequence a layer; products with the weights a
    token meets here, and a score and a weighted sum of `head_dim` for
    every query head over every row read.  Whatever implements the step
    has to do this much."""
    w = widths(config)
    flops = 2.0 * active_params(config) * batch + \
        4.0 * w["heads"] * w["head_dim"] * rows_read
    nbytes = (fixed_params(config)
              + w["layers"] * experts_touched * expert_params(config)) \
        * itemsize + (rows_read + batch * w["layers"]) * \
        row_bytes(config, itemsize)
    return flops, nbytes


def prefill_chunk_cost(config: dict, chunk: int, rows_read: float,
                       itemsize: int = 2):
    """(operations, bytes) one prefill chunk of `chunk` tokens needs when
    each of its queries attends `rows_read` rows, summed over the
    layers, on average: the fixed weights once and every held expert the
    chunk's `chunk x top_k x held / experts` assignments can touch, the
    chunk's rows written, the rows its last query attends read once;
    products with the weights a token meets here, and scores and
    weighted sums over the rows read."""
    w = widths(config)
    touched = min(w["held"], chunk * w["top_k"] * w["held"] / w["experts"])
    flops = 2.0 * active_params(config) * chunk + \
        4.0 * w["heads"] * w["head_dim"] * chunk * rows_read
    nbytes = (fixed_params(config)
              + w["layers"] * touched * expert_params(config)) * itemsize + \
        (rows_read + chunk / 2.0 * w["layers"] + chunk * w["layers"]) * \
        row_bytes(config, itemsize)
    return flops, nbytes
