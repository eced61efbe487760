"""chip_smoke.py, rehearsed off the chip (on-chip-measurement §2, first
and second rehearsal): its phases at toy size through the functions the
script is made of, kernels under the Pallas interpreter, the four-chip
phase on four of the virtual CPU devices — and the plumbing around it:
the device check that refuses anything but a TPU, and the compile-cache
helper.  The script itself has no CPU mode.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TOY = dict(size="nano", depth=2, seq=128)


def test_train_phase_toy():
    out = chip_smoke.train_phase(**TOY, micro=2, steps=3, on_chip=False)
    assert out["losses"][-1] < out["losses"][0]
    assert out["pallas_interpret"] and not out["flash_in_lowered_step"]


def test_train_phase_misses_the_flash_kernel_off_chip():
    with pytest.raises(RuntimeError, match="flash kernel is not in"):
        chip_smoke.train_phase(**TOY, micro=2, steps=1)


def test_serve_phase_toy():
    serve = dict(block_size=4, max_batch=4, max_seq_len=128,
                 prefill_chunk=16, num_blocks=1 + 4 * 32)
    out = chip_smoke.serve_phase(
        **TOY, serve=serve, prompt_lens=(3, 5, 9, 12, 17, 20, 26, 40),
        max_new=6, param_dtype=jax.numpy.float32)
    assert out["requests"] == 8 and all(out["first_token_equals_generate"])
    assert out["joined_while_decoding"] > 0
    assert out["token_agreement_share"] == 1.0  # fp32 on CPU: exact
    assert out["first_token_gap_to_top_logit"] == [0.0] * 8
    with pytest.raises(RuntimeError, match="beyond a near tie"):
        chip_smoke.serve_phase(
            **TOY, serve=serve, prompt_lens=(3, 5, 9, 12, 17, 20, 26, 40),
            max_new=6, margin=-1.0, param_dtype=jax.numpy.float32)


def test_evabyte_phase_toy():
    model = dict(num_layers=2, num_heads=4, d_model=64, d_ff=176,
                 window_size=32, chunk_size=4, attn_out_std=0.3)
    serve = dict(block_size=4, max_batch=3, max_seq_len=128,
                 prefill_chunk=8, num_blocks=1 + 3 * (8 + 8),
                 prefix_cache=False)
    out = chip_smoke.evabyte_phase(
        model=model, serve=serve, prompt_lens=(70, 64, 9), max_new=8,
        gap=1e-4, param_dtype=jax.numpy.float32)
    assert out["window_closes"] == 2 + 2 + 0
    assert out["worst_gap_to_top_logit"] <= 1e-4
    assert out["top1_agreement"] == 1.0  # fp32 on CPU: exact
    with pytest.raises(RuntimeError, match="below the reference"):
        chip_smoke.evabyte_phase(
            model=model, serve=serve, prompt_lens=(70,), max_new=8,
            gap=-1.0, param_dtype=jax.numpy.float32)


def test_kernels_phase_toy():
    out = chip_smoke.kernels_phase(batch=1, seq=256, heads=2, head_dim=64,
                                   d_model=64, vocab=1024,
                                   paged_shapes=((5, 64), (2, 128)),
                                   paged_slots=3, paged_width=4,
                                   bert_batch=2, bert_seq=256, bert_heads=2,
                                   eva_heads=(2, 64), eva_window=32,
                                   eva_chunk=4, eva_summary_blocks=8,
                                   eva_positions=(0, 31, 32, 100, -1),
                                   grouped_shapes=((6, 8, 2, 64, 4, 1 / 64),
                                                   (3, 4, 2, 16, 3, None)),
                                   sliding_shape=(4, 2, 16, 6, 32),
                                   sliding_positions=(200, 5, 31, 32, 95, 96,
                                                      -1),
                                   prefill_shape=(4, 2, 128, 6, 16),
                                   prefill_starts=(0, 21, 88),
                                   sliding_chunk_shape=(4, 2, 128, 6, 32,
                                                        16),
                                   sliding_chunk_starts=(0, 32, 48, 53, 200),
                                   latent_shape=(3, 4, 32, 16, 3),
                                   routed_shape=(16, 16, 128, 256),
                                   routed_live=2,
                                   slab_shapes=((160, 4, 4, 16, 128, 256),),
                                   ssm_shape=(6, 4, 8, 16),
                                   ssm_live=3, gdn_shape=(6, 4, 16),
                                   gdn_live=3,
                                   relu2_shape=(16, 16, 128, 256),
                                   relu2_live=2,
                                   relu2_slab=(160, 4, 4, 16, 128, 128),
                                   ssm_groups_shape=(6, 4, 8, 16, 2),
                                   ssm_groups_live=3,
                                   masked_shape=(16, 2, 24, 8, 16, 32),
                                   masked_table=(4, 16, 16, 12),
                                   masked_start=20,
                                   conv_shape=(6, 32, 3, 160), conv_live=4,
                                   held_shape=(16, 4, 4, 16, 128, 256),
                                   held_live=12,
                                   held_slab=(160, 4, 4, 16, 128, 256),
                                   on_chip=False)
    assert [k["kernel"] for k in out["kernels"]] == [
        "flash_attention_fwd", "flash_attention_bwd",
        "flash_attention_full_bias_dropout_fwd",
        "flash_attention_full_bias_dropout_bwd", "fused_xent_fwd",
        "fused_xent_bwd", "paged_attention_dense_H5_Dh64",
        "paged_attention_dense_H2_Dh128", "eva_attention_bf16_H2_Dh64",
        "grouped_attention_bf16_H8_KV2_Dh64",
        "grouped_attention_bf16_H4_KV2_Dh16",
        "sliding_attention_bf16_H4_KV2_Dh16_ring6",
        "grouped_prefill_bf16_H4_KV2_Dh128_at0",
        "grouped_prefill_bf16_H4_KV2_Dh128_at21",
        "grouped_prefill_bf16_H4_KV2_Dh128_at88",
        *(f"sliding_prefill_bf16_H4_KV2_Dh128_ring6_at{start}"
          for start in (0, 32, 48, 53, 200)),
        "latent_attention_bf16_H4_W48",
        "touched_experts_bf16_T16_E16", "touched_experts_bf16_T16_E16_relu2",
        "grouped_experts_bf16_T160_E4of16",
        "grouped_experts_bf16_T160_E4of16_relu2",
        "ssm_step_B6_H4_P8_N16_live3", "ssm_step_B6_H4_P8_N16_live3_G2",
        "gdn_step_B6_H4_D16_live3",
        "masked_latent_attention_bf16_T16_H2_tiles3",
        "conv_mix_bf16_D32_step_B6_live4",
        "conv_mix_bf16_D32_chunk_T160_valid60",
        "touched_experts_bf16_T16_E4of16",
        "grouped_experts_bf16_T160_E4of16_sigmoid"]
    # the CPU keeps the two slices right; the chip's answer is the phase's
    assert out["own_lanes_two_slices_right"] is True


def test_four_chip_phase_on_four_virtual_devices():
    out = chip_smoke.four_chip_phase(**TOY, micro=1, steps=3, on_chip=False)
    assert out["optimizer_state_devices"] == [0, 1, 2, 3]
    assert out["grad_accumulator_devices"] == [0, 1, 2, 3]
    assert out["collectives_in_step"]


def test_script_refuses_a_cpu_backend():
    """Non-zero exit, and the last line is the failure the contract
    prescribes: never `"ok": true` without a TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["phase"] == "device"
    assert "needs a TPU" in last["error"]
    assert '"ok": true' not in r.stdout


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_dir", "in_checkout"])
def test_compile_cache_dir(placed, monkeypatch, tmp_path):
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if placed:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(os.path.abspath(ROOT), ".jax_cache")
            assert os.path.abspath(enable_compile_cache()) == want
            assert jax.config.jax_compilation_cache_dir == \
                enable_compile_cache()
    finally:
        # the tests run with the cache off (conftest.py)
        jax.config.update("jax_compilation_cache_dir", before)
