"""The EvaByte family: how a configuration file becomes the program's
model object, and the arithmetic of what a serving step has to move and
compute.  The arithmetic is the benchmark's own."""

from __future__ import annotations


def widths(config: dict) -> dict:
    d = config["hidden_size"]
    return {"d_model": d, "heads": config["num_attention_heads"],
            "head_dim": d // config["num_attention_heads"],
            "d_ff": config["intermediate_size"],
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "pred_heads": config["num_pred_heads"],
            "window": config["window_size"], "chunk": config["chunk_size"]}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object (`deepspeed_tpu.models.EvaByte`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import EvaByte, EvaByteConfig

    w = widths(config)
    if seq_len > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq_len} exceeds "
                         f"max_position_embeddings")
    if config["num_key_value_heads"] != w["heads"]:
        raise ValueError("EvaByte has full heads")
    if n_dev != 1:
        raise ValueError("one pipeline stage is served on one chip")
    init = config["assumed"]["init"]
    return EvaByte(EvaByteConfig(
        vocab_size=w["vocab"], max_seq_len=seq_len, num_layers=w["layers"],
        num_heads=w["heads"], d_model=w["d_model"], d_ff=w["d_ff"],
        window_size=w["window"], chunk_size=w["chunk"],
        num_pred_heads=w["pred_heads"], rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]), init_std=init["std"],
        attn_out_std=init["attn_out_std"], pool_std=init["pool_std"],
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Byte ids are drawn below this: the published vocabulary (bytes
    plus specials)."""
    return config["vocab_size"]


def matmul_params(config: dict) -> int:
    """Parameters that multiply every byte: q, k, v, o and the three FFN
    matrices of every layer, and the 8 x 320 output head once.  The
    embedding is looked up; norms and pooling vectors are vectors."""
    w = widths(config)
    d, f = w["d_model"], w["d_ff"]
    return w["layers"] * (4 * d * d + 3 * d * f) + \
        d * w["pred_heads"] * w["vocab"]


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """One cache row (a key and a value, exact or summary) in all the
    layers held here."""
    w = widths(config)
    return 2 * w["layers"] * w["heads"] * w["head_dim"] * itemsize


def decode_step_cost(config: dict, rows_read: float, batch: float,
                     itemsize: int = 2):
    """(operations, bytes) ONE decode step needs for `batch` sequences
    that together read `rows_read` cache rows (window rows up to the
    query plus visible summary rows): the weights once, those rows once,
    one new row a sequence; products with the weights for `batch` bytes
    and scores and weighted sums over the rows read."""
    w = widths(config)
    flops = 2.0 * matmul_params(config) * batch + \
        4.0 * rows_read * w["layers"] * w["heads"] * w["head_dim"]
    nbytes = matmul_params(config) * itemsize + \
        (rows_read + batch) * row_bytes(config, itemsize)
    return flops, nbytes


def prefill_chunk_cost(config: dict, chunk: int, rows_read: float,
                       itemsize: int = 2):
    """(operations, bytes) one prefill chunk of `chunk` bytes needs when
    its queries read `rows_read` rows each on average: the weights once,
    the chunk's rows written, the window and summary rows read once."""
    w = widths(config)
    flops = 2.0 * matmul_params(config) * chunk + \
        4.0 * chunk * rows_read * w["layers"] * w["heads"] * w["head_dim"]
    nbytes = matmul_params(config) * itemsize + \
        (rows_read + chunk + chunk / w["chunk"]) * row_bytes(config, itemsize)
    return flops, nbytes
