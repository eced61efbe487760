"""The GPT-2 family: how a configuration file becomes the program's model
object, and the arithmetic of what that model has to compute.  The
arithmetic is the benchmark's own (not profiling/flops_profiler, which
counts the jaxpr, recomputation included)."""

from __future__ import annotations


def widths(config: dict) -> dict:
    d = config["n_embd"]
    return {"d_model": d, "heads": config["n_head"],
            "head_dim": d // config["n_head"],
            "d_ff": config.get("n_inner") or 4 * d,
            "layers": config["n_layer"],
            "vocab": config["vocab_size"],
            "padded_vocab": config["assumed"]["padded_vocab_size"]}


def build(config: dict, *, seq_len: int, n_dev: int, param_dtype=None,
          **overrides):
    """The program's own model object (`deepspeed_tpu.models.GPT`)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import GPT
    from deepspeed_tpu.models.gpt import GPTConfig

    w = widths(config)
    if seq_len > config["n_positions"]:
        raise ValueError(f"seq_len {seq_len} exceeds n_positions")
    return GPT(GPTConfig(
        vocab_size=w["padded_vocab"], max_seq_len=seq_len,
        num_layers=w["layers"], num_heads=w["heads"], d_model=w["d_model"],
        d_ff=w["d_ff"], dropout=config["assumed"]["dropout"],
        layer_norm_eps=config["layer_norm_epsilon"],
        tie_embeddings=config["tie_word_embeddings"],
        shard_activations=n_dev > 1,
        param_dtype=jnp.dtype(param_dtype or "float32"), **overrides))


def prompt_vocab(config: dict) -> int:
    """Token ids are drawn below this: the published vocabulary."""
    return config["vocab_size"]


def matmul_params(config: dict) -> int:
    """Parameters that multiply every token: the block weights (QKV,
    projection, two MLP matrices) and the output head, once.  The position
    table is looked up, not multiplied; biases and norms are vectors."""
    w = widths(config)
    d, f = w["d_model"], w["d_ff"]
    return w["layers"] * (3 * d * d + d * d + 2 * d * f) + d * w["vocab"]


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE for one trained
    token: 6 per multiplying parameter, plus attention's two S x S matrix
    products (12 * L * S * d forward and backward), halved because half of
    a causal score matrix is never needed.  Recomputation does not count."""
    w = widths(config)
    attn = 12 * w["layers"] * seq_len * w["d_model"]
    return 6.0 * matmul_params(config) + attn / 2


def flash_attention_cost(config: dict, batch: int, seq_len: int,
                         itemsize: int = 2):
    """(operations, bytes) one training step's flash kernels need, all
    layers, forward and backward.  Forward: QK^T and PV, 4*S*S*Dh a head.
    Backward: the score matrix again, dP, dV, dK and dQ, 10*S*S*Dh (the
    FlashAttention papers' count).  Half of it where causal.  Bytes:
    forward reads q, k, v and writes o and one fp32 log-sum-exp a row;
    backward reads q, k, v, o, do and the log-sum-exp, writes dq, dk, dv."""
    w = widths(config)
    bh = batch * w["heads"]
    mm = 2.0 * bh * seq_len * seq_len * w["head_dim"] / 2  # causal half
    tensor = bh * seq_len * w["head_dim"] * itemsize
    lse = bh * seq_len * 4
    flops = (2 + 5) * mm
    nbytes = (4 * tensor + lse) + (8 * tensor + lse)
    return w["layers"] * flops, w["layers"] * nbytes
