"""Rehearsal of the DeepSeek-V2 serving cell on the CPU at toy size (the
real runner, generator, family module, reference and the new per-layer
metrics' files), and the family's arithmetic against counts worked out by
hand.  Nothing here is a device number."""

import json

import pytest

from benchmarks import harness
from benchmarks.tests import toy

DEEPSEEK = {"family": "deepseek_v2", "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 16, "v_head_dim": 16, "q_lora_rank": None,
            "intermediate_size": 96, "moe_intermediate_size": 48,
            "n_routed_experts": 8, "num_experts_per_tok": 3,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "num_hidden_layers": 3, "vocab_size": 128, "rms_norm_eps": 1e-6,
            "rope_theta": 10000, "max_position_embeddings": 512,
            "rope_scaling": {"type": "yarn", "factor": 40,
                             "original_max_position_embeddings": 64,
                             "beta_fast": 32, "beta_slow": 1,
                             "mscale": 0.707, "mscale_all_dim": 0.707},
            "attention_bias": False, "hidden_act": "silu",
            "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
            "topk_method": "greedy", "scoring_func": "softmax",
            "norm_topk_prob": False, "routed_scaling_factor": 1,
            "tie_word_embeddings": False, "reduced": [],
            "assumed": {"init": {"std": 0.2, "router_std": 1.0}}}
SERVE = {"runner": "serve",
         "serve": {"block_size": 4, "num_blocks": 97, "max_batch": 4,
                   "prefill_chunk": 16, "max_seq_len": 128,
                   "prefix_cache": False},
         "model": {"param_dtype": "bfloat16"}, "drain_seconds": 30,
         "check": {"requests": 6, "batch": 1, "logit_margin": 0.25},
         "trace": {"seconds": 0.3}}
CHATGEN = {"generator": "poisson_lengths", "rate_rps": 12.0,
           "prompt_tokens": [8, 60], "output_tokens": [8, 30],
           "max_total_tokens": 128, "shape_seed": 7}
CELL = "deepseek-v2-lite-d9.serve.chatgen"


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


@pytest.mark.parametrize("trace", [False, True])
def test_deepseek_serving_cell_runs_and_matches_its_reference(tmp_path,
                                                              trace):
    from benchmarks.runners import serve

    cell = toy.cell(DEEPSEEK, SERVE, CHATGEN, tmp=tmp_path, trace=trace,
                    seconds=1.0)
    run = serve.run(cell)
    load, check = run.notes
    assert run.correct and run.failed == 0, run.notes
    assert load["compiles_in_window"] == 0
    assert check["requests"] == 6
    rows = _read("mla_rows_per_query.serve", cell, run)
    touched = _read("moe_experts_touched_per_layer.serve", cell, run)
    assert 8 <= rows <= 128
    assert 3 <= touched <= 8           # top 3 of 8, up to 4 slots
    pairs = run.counters["serve.moe.assignments"]
    tokens = run.counters["serve.prefill_chunks"]["bytes"] + \
        run.counters["serve.decode_steps"]["bytes"]
    assert pairs["bytes"] == tokens * 3 * 2      # nothing dropped
    # the roofline share needs a device trace: nothing to read here
    assert _read("moe_decode_hbm_roofline.serve", cell, run) is None


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "deepseek_v2")
    config = harness.load_json("configs", "deepseek-v2-lite-d9.json")
    # attention: W_q 2048 x 16 x 192, W_kv_a 2048 x 576, W_kv_b 512 x 16 x
    # 256, W_o 2048 x 2048
    assert family.attention_params(config) == \
        6_291_456 + 1_179_648 + 2_097_152 + 4_194_304 == 13_762_560
    assert family.expert_params(config) == 3 * 2048 * 1408 == 8_650_752
    # an expert layer: 64 experts, 2 shared, attention, the router
    layer = 64 * 8_650_752 + 2 * 8_650_752 + 13_762_560 + 2048 * 64
    assert layer == 584_843_264
    dense = 3 * 2048 * 10944 + 13_762_560
    assert dense == 81_002_496
    assert family.held_params(config) == \
        8 * layer + dense + 2 * 2048 * 102400 == 5_179_179_008
    assert family.fixed_params(config) == \
        9 * 13_762_560 + 67_239_936 + 8 * (131_072 + 17_301_504) \
        + 209_715_200 == 540_278_784
    assert family.active_params(config) == \
        540_278_784 + 8 * 6 * 8_650_752 == 955_514_880
    # a row: 576 values in 9 layers, bf16
    assert family.row_bytes(config) == 9 * 576 * 2 == 10_368
    flops, nbytes = family.decode_step_cost(
        config, rows_read=32 * 2000, batch=32, experts_touched=60)
    # the fixed weights, 60 experts of 17.3 MB in 8 layers, 64,032 rows
    assert nbytes == 2 * 540_278_784 + 8 * 60 * 17_301_504 \
        + 64_032 * 10_368 == 10_049_163_264
    assert flops / 197e12 < nbytes / 819e9     # HBM-bound
    assert nbytes / 819e9 > 0.0116             # the issue's >= 11.6 ms
    few = family.decode_step_cost(config, 2000, 1, 6)[1]
    assert few == 2 * 540_278_784 + 8 * 6 * 17_301_504 + 2001 * 10_368
    pflops, pbytes = family.prefill_chunk_cost(config, chunk=512,
                                               rows_read=1000)
    assert pbytes == 2 * (540_278_784 + 8 * 64 * 8_650_752) \
        + (1256 + 512) * 10_368
    assert pflops > 2 * 955_514_880 * 512
    # 6 per active parameter and half of the causal products
    assert family.model_flops_per_token(config, 4096) == \
        6 * 955_514_880 + 6 * 9 * 4096 * 16 * 320 / 2


def test_build_gives_the_published_widths_and_refuses_the_rest():
    family = harness.plugin("models", "deepseek_v2")
    config = harness.load_json("configs", "deepseek-v2-lite-d9.json")
    c = family.build(config, seq_len=4096, n_dev=1,
                     param_dtype="bfloat16").config
    assert (c.num_layers, c.d_model, c.num_heads, c.kv_lora_rank,
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.d_ff,
            c.first_k_dense, c.num_experts, c.top_k, c.num_shared_experts,
            c.d_expert, c.vocab_size, c.latent_width) == \
        (9, 2048, 16, 512, 128, 64, 128, 10944, 1, 64, 6, 2, 1408, 102400,
         576)
    assert c.yarn.factor == 40 and c.yarn.mscale_all_dim == 0.707
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(config, seq_len=1 << 20, n_dev=1)
    with pytest.raises(ValueError, match="share of the experts"):
        family.build(config, seq_len=4096, n_dev=4)
    with pytest.raises(ValueError, match="q_lora_rank"):
        family.build(dict(config, q_lora_rank=1536), seq_len=4096, n_dev=1)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.build(dict(config, norm_topk_prob=True), seq_len=4096,
                     n_dev=1)


def test_the_configuration_file_keeps_every_published_size():
    config = harness.load_json("configs", "deepseek-v2-lite-d9.json")
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(json.loads(line) for line in f
                       if '"name": "DeepSeek-V2-Lite"' in line)
    except OSError:
        pytest.skip("the catalog is not on this machine")
    differ = [k for k, v in row["config"].items() if config.get(k) != v]
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 27}
    assert config["source"] == row["source_url"]


def test_the_cell_is_in_the_benchmark_with_its_metrics():
    """Looked up by name: what other cells and metrics the benchmark
    holds, and in which order, is not this test's."""
    with open(harness.BENCH + "/../BENCHMARK.json") as f:
        bm = json.load(f)
    entry = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 0
    cell = harness.load_json("workloads", CELL + ".json")
    assert cell["serve"] == dict(
        cell["serve"], block_size=16, num_blocks=8193, max_batch=32,
        max_seq_len=4096, admission="continuous", prefix_cache=False,
        kv_dtype="bf16")
    assert cell["serve"]["prefill_chunk"] in (256, 512, 1024)
    mix = harness.load_json("traffic", entry["traffic"] + ".json")
    assert mix["prompt_tokens"] == [256, 2048]
    assert mix["output_tokens"] == [256, 1024]
    assert mix["shape_seed"] == 20260929 and mix["max_total_tokens"] == 4096
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in ("mla_rows_per_query.serve",
                 "moe_experts_touched_per_layer.serve",
                 "moe_decode_hbm_roofline.serve"):
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_itl_p95_ms"
    # of 80 requests the first-token tail is the fifth-largest time and its
    # sets of six spread 1.6-18 % (PERF.md section 2, PR 52): the cell reports
    # the other two, and the prefill chunk under the name that moves the gap
    for name, listed in (("serve_ttft_p95_ms", False),
                         ("serve_itl_p95_ms", True),
                         ("serve_tokens_per_s", True)):
        assert (CELL in next(m for m in bm["end_to_end"]
                             if m["name"] == name)["workloads"]) is listed
    for name, listed in (("prefill_chunk_ms.gap", True),
                         ("prefill_chunk_ms.serve", False),
                         ("queue_wait_p95_ms.serve", False)):
        assert (CELL in by_name[name]["workloads"]) is listed, name
    assert mix["rate_rps"] == 1.6
