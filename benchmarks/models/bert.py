"""The BERT family: configuration file to the program's model object, and
the arithmetic of what that model has to compute."""

from __future__ import annotations


def widths(config: dict) -> dict:
    d = config["hidden_size"]
    return {"d_model": d, "heads": config["num_attention_heads"],
            "head_dim": d // config["num_attention_heads"],
            "d_ff": config["intermediate_size"],
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "padded_vocab": config["assumed"]["padded_vocab_size"]}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object (`deepspeed_tpu.models.Bert`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import Bert
    from deepspeed_tpu.models.bert import BertConfig

    w = widths(config)
    if seq_len > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq_len} exceeds max_position_embeddings")
    # the position table keeps its published 512 rows; a batch uses seq_len
    return Bert(BertConfig(
        vocab_size=w["padded_vocab"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=w["layers"], num_heads=w["heads"], d_model=w["d_model"],
        d_ff=w["d_ff"], type_vocab_size=config["type_vocab_size"],
        attn_dropout=config["attention_probs_dropout_prob"],
        hidden_dropout=config["hidden_dropout_prob"],
        layer_norm_eps=config["layer_norm_eps"],
        initializer_range=config["initializer_range"],
        pre_layer_norm=config["assumed"]["pre_layer_norm"],
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    return config["vocab_size"]


def matmul_params(config: dict, seq_len: int) -> float:
    """Parameters that multiply every token: the block weights, the MLM
    head's transform and its tied decoder, once; the pooler and the NSP
    head multiply one position in `seq_len`.  Position and token-type
    tables are looked up, not multiplied."""
    w = widths(config)
    d, f = w["d_model"], w["d_ff"]
    per_token = w["layers"] * (3 * d * d + d * d + 2 * d * f) \
        + d * d + d * w["vocab"]
    return per_token + (d * d + 2 * d) / seq_len


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """6 per multiplying parameter plus bidirectional attention's
    12 * L * S * d; recomputation does not count."""
    w = widths(config)
    return 6.0 * matmul_params(config, seq_len) \
        + 12 * w["layers"] * seq_len * w["d_model"]
