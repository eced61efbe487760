"""Config-system tests (reference analogue: tests/unit/test_config.py,
test_ds_config.py)."""

import json

import pytest

from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError


def cfg(d, world_size=2):
    return DeepSpeedConfig(d, world_size=world_size)


def test_batch_triple_all_given():
    c = cfg({"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4,
             "gradient_accumulation_steps": 4})
    assert c.train_batch_size == 32


def test_batch_triple_inconsistent():
    with pytest.raises(DeepSpeedConfigError):
        cfg({"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4,
             "gradient_accumulation_steps": 2})


@pytest.mark.parametrize("d,expect", [
    ({"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4}, (32, 4, 4)),
    ({"train_batch_size": 32, "gradient_accumulation_steps": 4}, (32, 4, 4)),
    ({"train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 4},
     (32, 4, 4)),
    ({"train_batch_size": 32}, (32, 16, 1)),
    ({"train_micro_batch_size_per_gpu": 16}, (32, 16, 1)),
])
def test_batch_triple_derivation(d, expect):
    c = cfg(d)
    assert (c.train_batch_size, c.train_micro_batch_size_per_gpu,
            c.gradient_accumulation_steps) == expect


def test_batch_triple_missing():
    with pytest.raises(DeepSpeedConfigError):
        cfg({"gradient_accumulation_steps": 4})


def test_precision_fp16_bf16():
    assert cfg({"train_batch_size": 2}).precision == "float32"
    assert cfg({"train_batch_size": 2,
                "fp16": {"enabled": True}}).precision == "float16"
    assert cfg({"train_batch_size": 2,
                "fp16": {"enabled": True, "type": "bfloat16"}}).precision == "bfloat16"
    with pytest.raises(DeepSpeedConfigError):
        cfg({"train_batch_size": 2, "fp16": {"enabled": True, "type": "fp8"}})


def test_loss_scale_params():
    c = cfg({"train_batch_size": 2,
             "fp16": {"enabled": True, "loss_scale": 0,
                      "initial_scale_power": 16, "loss_scale_window": 500,
                      "hysteresis": 3, "min_loss_scale": 2}})
    assert c.loss_scale == 0 and c.initial_scale_power == 16
    assert c.loss_scale_window == 500 and c.hysteresis == 3
    assert c.min_loss_scale == 2


def test_zero_config_defaults_and_stage():
    c = cfg({"train_batch_size": 2})
    assert c.zero_optimization_stage == 0 and not c.zero_enabled
    c = cfg({"train_batch_size": 2, "zero_optimization": {"stage": 2}})
    assert c.zero_enabled and c.zero_config.stage == 2
    assert c.zero_config.reduce_bucket_size == 500000000
    c = cfg({"train_batch_size": 2, "zero_optimization": True})
    assert c.zero_config.stage == 1


def test_zero_offload_legacy_and_new():
    c = cfg({"train_batch_size": 2,
             "zero_optimization": {"stage": 2, "cpu_offload": True}})
    assert c.zero_config.offload_optimizer.device == "cpu"
    c = cfg({"train_batch_size": 2,
             "zero_optimization": {"stage": 3,
                                   "offload_param": {"device": "nvme",
                                                     "nvme_path": "/tmp/nv"}}})
    assert c.zero_config.offload_param.device == "nvme"
    assert not c.zero_config.cpu_offload_params


def test_zero_invalid_stage():
    with pytest.raises(ValueError):
        cfg({"train_batch_size": 2, "zero_optimization": {"stage": 5}})


def test_optimizer_scheduler_sections():
    c = cfg({"train_batch_size": 2,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
             "scheduler": {"type": "WarmupLR",
                           "params": {"warmup_num_steps": 10}}})
    assert c.optimizer_name == "adam"
    assert c.optimizer_params["lr"] == 1e-3
    assert c.scheduler_name == "WarmupLR"
    assert c.scheduler_params["warmup_num_steps"] == 10


def test_json_file_and_duplicate_keys(tmp_path):
    p = tmp_path / "ds.json"
    p.write_text(json.dumps({"train_batch_size": 8}))
    assert DeepSpeedConfig(str(p), world_size=2).train_batch_size == 8
    p2 = tmp_path / "dup.json"
    p2.write_text('{"train_batch_size": 8, "train_batch_size": 16}')
    with pytest.raises(ValueError):
        DeepSpeedConfig(str(p2), world_size=2)
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig(str(tmp_path / "missing.json"), world_size=2)


def test_aux_sections():
    c = cfg({"train_batch_size": 2,
             "activation_checkpointing": {"partition_activations": True,
                                          "number_checkpoints": 4},
             "flops_profiler": {"enabled": True, "profile_step": 5},
             "progressive_layer_drop": {"enabled": True, "gamma": 0.01},
             "tensorboard": {"enabled": True, "output_path": "/tmp/tb"},
             "wall_clock_breakdown": True})
    assert c.activation_checkpointing_config.partition_activations
    assert c.activation_checkpointing_config.number_checkpoints == 4
    assert c.flops_profiler_config.enabled
    assert c.flops_profiler_config.profile_step == 5
    assert c.pld_enabled and c.pld_params["gamma"] == 0.01
    assert c.tensorboard_enabled and c.tensorboard_output_path == "/tmp/tb"
    assert c.wall_clock_breakdown


def test_checkpoint_tag_validation_modes():
    c = cfg({"train_batch_size": 2})
    assert c.checkpoint_tag_validation_enabled
    assert not c.checkpoint_tag_validation_fail
    c = cfg({"train_batch_size": 2, "checkpoint": {"tag_validation": "FAIL"}})
    assert c.checkpoint_tag_validation_fail


def test_mesh_section():
    c = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "mesh": {"data": 2, "model": 4}})
    assert c.mesh_shape == {"data": 2, "model": 4}
    assert c.world_size == 2  # from explicit data axis


def test_top_level_bf16_section_enables_bfloat16():
    """`{"bf16": {"enabled": true}}` (later-DeepSpeed spelling) must select
    bfloat16 compute — it was previously ignored, silently training fp32."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "bf16": {"enabled": True}}, world_size=1)
    assert cfg.precision == "bfloat16"
    cfg2 = DeepSpeedConfig({"train_batch_size": 8,
                            "bf16": {"enabled": False}}, world_size=1)
    assert cfg2.precision == "float32"
    cfg3 = DeepSpeedConfig({"train_batch_size": 8,
                            "fp16": {"enabled": True,
                                     "type": "bfloat16"}}, world_size=1)
    assert cfg3.precision == "bfloat16"


def test_bf16_and_fp16_both_enabled_raises():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)

    with pytest.raises(DeepSpeedConfigError, match="both"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "bf16": {"enabled": True},
                         "fp16": {"enabled": True}}, world_size=1)



def test_kernels_block_is_rejected_by_name():
    """The block is gone: which kernel a call runs is no user setting.
    A config that still carries it is told so, and where the choice
    lives, instead of being silently ignored."""
    with pytest.raises(DeepSpeedConfigError, match="'kernels'") as e:
        DeepSpeedConfig({"train_batch_size": 8,
                         "kernels": {"impl": "jnp"}}, world_size=1)
    assert "kernels/registry.py" in str(e.value)
    assert "DS_KERNEL_" in str(e.value)
