"""Toy cells for the CPU rehearsal: the real runners, generators, family
modules and references at sizes the CPU runs in seconds.  Nothing here is
a device number."""

import time

from benchmarks.harness import Cell, plugin

GPT = {"family": "gpt", "n_embd": 64, "n_head": 4, "n_layer": 2,
       "n_positions": 64, "vocab_size": 250, "layer_norm_epsilon": 1e-5,
       "tie_word_embeddings": True, "reduced": [],
       "assumed": {"padded_vocab_size": 256, "dropout": 0.0}}
BERT = {"family": "bert", "hidden_size": 64, "num_attention_heads": 4,
        "num_hidden_layers": 2, "intermediate_size": 256,
        "max_position_embeddings": 64, "type_vocab_size": 2,
        "vocab_size": 250, "hidden_dropout_prob": 0.1,
        "attention_probs_dropout_prob": 0.1, "layer_norm_eps": 1e-12,
        "initializer_range": 0.02, "reduced": [],
        "assumed": {"padded_vocab_size": 256, "pre_layer_norm": True,
                    "mask_token_id": 103}}
ENGINE = {"bf16": {"enabled": True},
          "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
          "zero_optimization": {"stage": 2}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def cell(config, workload, traffic, *, tmp, chips=1, seed=2 ** 31 + 11,
         seconds=0.5, trace=False):
    import jax

    return Cell(name="toy", chips=chips, seed=seed, seconds=seconds,
                trace=trace, workload=workload, config=config,
                traffic=traffic, family=plugin("models", config["family"]),
                generator=plugin("traffic", traffic["generator"]),
                peaks=PEAKS, devices=jax.devices()[:chips], scratch=str(tmp),
                t_start=time.perf_counter(), compiles=[])


def train_workload(check_loss, warmup=1):
    step = {"step_rtol": 0.2} if check_loss == "first_step" else {
        "update": {"size": [0.9, 1.1], "down_gradient": [0.5, 1.02],
                   "down_gradient_least": [0.3, 1.02]}}
    return {"runner": "train", "engine": ENGINE, "warmup_steps": warmup,
            "check": {"loss": check_loss, "rtol": 2e-3, **step},
            "trace": {"skip_steps": 1, "steps": 2}}


SERVE = {"runner": "serve",
         "serve": {"block_size": 4, "num_blocks": 65, "max_batch": 4,
                   "prefill_chunk": 16, "max_seq_len": 64},
         "model": {"param_dtype": "bfloat16"}, "drain_seconds": 20,
         "check": {"requests": 64, "batch": 4, "logit_margin": 0.2},
         "trace": {"seconds": 0.3}}
CHAT = {"generator": "poisson_lengths", "rate_rps": 20.0,
        "prompt_tokens": [4, 40], "output_tokens": [2, 12],
        "max_total_tokens": 64, "shape_seed": 5}
