"""Rehearsal of the Nemotron-H serving cell on the CPU at toy size (the
real runner, generator, family module, reference and the new per-layer
metrics' files), and the family's arithmetic against a parameter tree's
real counts and counts worked out by hand.  Nothing here is a device
number."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks.tests import toy

NEMOTRON = {"family": "nemotron_h", "hidden_size": 32,
            "hybrid_override_pattern": "MEM*EMEM*E",
            "num_hidden_layers": 10, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "mamba_num_heads": 8, "mamba_head_dim": 4, "ssm_state_size": 8,
            "n_groups": 4, "conv_kernel": 4, "chunk_size": 4,
            "moe_intermediate_size": 16,
            "moe_shared_expert_intermediate_size": 24,
            "n_routed_experts": 4, "n_shared_experts": 1,
            "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
            "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
            "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "attention_bias": False, "mamba_proj_bias": False,
            "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
            "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
            "sliding_window": None, "vocab_size": 128,
            "max_position_embeddings": 512,
            "reduced": ["n_routed_experts", "vocab_size"],
            "published": {"n_routed_experts": 16, "vocab_size": 1024},
            "held": {"first_expert": 4, "first_vocab_row": 0},
            "assumed": {"init": {"std": 0.2, "bias_std": 0.05, "conv": 0.5,
                                 "A": [1.0, 16.0], "dt": [0.001, 0.5]}}}
SERVE = {"runner": "serve_agree",
         "serve": {"block_size": 4, "num_blocks": 129, "max_batch": 4,
                   "prefill_chunk": 8, "max_seq_len": 128,
                   "prefix_cache": False},
         "model": {"param_dtype": "bfloat16"}, "drain_seconds": 30,
         "check": {"requests": 6, "batch": 1, "logit_margin": 1.5,
                   "top1_agreement_floor": 0.7},
         "trace": {"seconds": 0.3}}
RATE = {"generator": "poisson_lengths", "rate_rps": 12.0,
        "prompt_tokens": [2, 60], "output_tokens": [4, 20],
        "max_total_tokens": 128, "shape_seed": 7}
CELL = "nemotron-3-nano-30b-a3b-e16.serve.reasoning"
CONFIG = "nemotron-3-nano-30b-a3b-e16"
STATE = 64 * 64 * 128 * 4 + 3 * 6144 * 2      # a Mamba-2 layer's, a slot
NEW = ("ssm_moe_decode_hbm_roofline.serve",
       "ssm_groups_live_state_mb_per_step.serve", "decode_state_kernel_ms.serve",
       "moe_touched_kernel_ms.serve")


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


@pytest.mark.parametrize("trace", [False, True])
def test_nemotron_h_serving_cell_runs_and_matches_its_reference(tmp_path,
                                                                trace):
    from benchmarks.runners import serve_agree

    cell = toy.cell(NEMOTRON, SERVE, RATE, tmp=tmp_path, trace=trace,
                    seconds=1.0)
    run = serve_agree.run(cell)
    load, check = run.notes
    assert check["top1_agreement"] >= check["top1_agreement_floor"] == 0.7
    assert run.correct and run.failed == 0, run.notes
    assert load["compiles_in_window"] == 0
    assert check["requests"] == 6
    steps = run.counters["serve.decode_steps"]
    # the two attention layers' rows: every cached position of each
    assert run.counters["serve.attn.rows_read"]["bytes"] >= \
        2 * 3 * run.counters["serve.attn.rows_read"]["calls"]
    assert run.counters["serve.ssm.state_resets"]["calls"] == \
        load["requests"]
    # what the program streams: all 4 slots' state in the 4 mixer layers,
    # in and out (the toy's sizes: 8 heads of 4 x 8 float32 and 3 kept
    # inputs of 32 + 2 x 4 x 8 at bf16); what the live slots need: the
    # published layer's bytes for every live slot and layer, in and out
    toy_state = 8 * 4 * 8 * 4 + 3 * 96 * 2
    assert _read("ssm_state_mb_per_step.serve", cell, run) == \
        pytest.approx(2 * 4 * 4 * toy_state / 1e6)
    live = _read("ssm_groups_live_state_mb_per_step.serve", cell, run)
    assert live == pytest.approx(
        2 * STATE / 1e6 * 4 * steps["bytes"] / steps["calls"])
    # 4 of the router's 16 experts are held, in the 4 expert layers (not
    # in 10 - dense_layers of them): at most 4 touched a layer
    touched = run.counters["serve.moe.experts_touched"]
    assert touched["calls"] == 4 * steps["calls"]
    assert 0 < touched["bytes"] <= 4 * touched["calls"]
    # the shares and times need a device trace: nothing to read here
    for name in NEW:
        if name != "ssm_groups_live_state_mb_per_step.serve":
            assert _read(name, cell, run) is None, name


def test_the_arithmetic_counts_a_parameter_tree(tmp_path):
    """`held_params` and the per-piece counts against the leaves of the
    tree `build` makes, at the toy widths and at the published ones
    (shapes only)."""
    import jax

    family = harness.plugin("models", "nemotron_h")
    for config in (NEMOTRON, harness.load_json("configs", CONFIG + ".json")):
        model = family.build(config, seq_len=64, n_dev=1)
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        size = lambda t: sum(a.size for a in jax.tree_util.tree_leaves(t))
        assert size(tree) == family.held_params(config)
        blocks = tree["blocks"]
        pattern = config["hybrid_override_pattern"]
        m, a, e = (pattern.index(c) for c in "M*E")
        assert size(blocks[m]) == family.mixer_params(config)
        assert size(blocks[a]) == family.attention_params(config)
        mlp = blocks[e]["mlp"]
        assert sorted(mlp["experts"]) == ["down", "up"]      # two matrices
        assert size(mlp["experts"]) == \
            config["n_routed_experts"] * family.expert_params(config)
        assert size(blocks[e]) - size(mlp["experts"]) == \
            family.expert_layer_fixed_params(config)
        state, conv = model.layer_spec().state_shapes
        assert 4 * size(jax.ShapeDtypeStruct(state[0], "float32")) + \
            2 * size(jax.ShapeDtypeStruct(conv[0], "bfloat16")) == \
            family.state_bytes(config)


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "nemotron_h")
    config = harness.load_json("configs", CONFIG + ".json")
    # W_in 2688 x 10,304, 4 taps and a bias over 6,144 channels, A_log, D
    # and dt_bias of 64, the gated norm's 4,096, W_out 4096 x 2688, a norm
    assert family.mixer_params(config) == 27_697_152 + 30_720 + 192 \
        + 4096 + 11_010_048 + 2688 == 38_744_896
    # W_q 2688 x 4096, W_k and W_v 2688 x 256, W_o 4096 x 2688, a norm
    assert family.attention_params(config) == 11_010_048 + 2 * 688_128 \
        + 11_010_048 + 2688 == 23_399_040
    assert family.expert_params(config) == 2 * 2688 * 1856 == 9_977_856
    assert family.expert_layer_fixed_params(config) == 344_064 + 128 \
        + 19_955_712 + 2688 == 20_302_592
    assert family.held_params(config) == 23 * 38_744_896 + 6 * 23_399_040 \
        + 23 * (20_302_592 + 16 * 9_977_856) + 2 * 44_040_192 + 2688 \
        == 5_258_420_544
    assert round(2 * family.held_params(config) / 1e9, 2) == 10.52
    # the whole model: all 128 experts, all 131,072 rows
    assert 23 * 38_744_896 + 6 * 23_399_040 + 23 * (
        20_302_592 + 128 * 9_977_856) + 2 * 352_321_536 + 2688 \
        == 31_577_940_288
    fixed = 5_258_420_544 - 23 * 16 * 9_977_856 - 16_384 * 2688
    assert family.fixed_params(config) == fixed == 1_542_529_344
    assert family.active_params(config) == fixed \
        + 23 * 6 * 16 / 128 * 9_977_856
    # a slot's state in a mixer layer, a token's rows in the six layers
    assert family.state_bytes(config) == 2_097_152 + 36_864 == STATE
    assert 6 * family.row_bytes(config) == 6144
    assert round(40 * 23 * STATE / 1e9, 2) == 1.96
    assert round(6401 * 16 * 6144 / 1e9, 2) == 0.63
    rows = 6 * 24 * 1400
    flops, nbytes = family.decode_step_cost(config, rows_read=rows,
                                            batch=24, experts_touched=11)
    # the fixed weights (3.09 GB), 11 touched experts of TWO matrices in
    # 23 layers (5.05 GB), 24 live slots' state in and out in 23 layers
    # (2.36 GB), the rows read and 24 x 6 written, 1,024 B each
    assert nbytes == 2 * (fixed + 23 * 11 * 9_977_856) \
        + 2 * 24 * 23 * STATE + (rows + 144) * 1024 == 10_696_393_344
    assert flops == 24 * (2 * family.active_params(config)
                          + 23 * 5 * 64 * 64 * 128) + 4 * 32 * 128 * rows
    assert flops / 197e12 < nbytes / 819e9             # HBM-bound
    assert 0.0125 < nbytes / 819e9 < 0.0135
    # no slot live, no expert touched: the fixed weights and a row
    assert family.decode_step_cost(config, 1, 0, 0)[1] == 2 * fixed + 1024
    pflops, pbytes = family.prefill_chunk_cost(config, chunk=512,
                                               rows_read=6 * 256)
    assert pbytes == 2 * (5_258_420_544 - 16_384 * 2688) + 2 * 23 * STATE \
        + (256 + 512) * 6 * 1024
    assert family.ssm_step_cost(config, 24) == (
        5 * 24 * 64 * 64 * 128, 8 * 24 * 64 * 64 * 128)
    assert family.touched_experts_cost(config, 24, 11) == (
        2 * 24 * 11 * 9_977_856, 11 * 9_977_856 * 2)
    assert family.grouped_experts_cost(config, 384)[1] == \
        16 * 9_977_856 * 2 + 384 * 2688 * 6
    assert family.prompt_vocab(config) == 16_384


def test_the_configuration_keeps_the_catalogs_widths():
    """Every number of the catalog's `config` under the same key, but the
    two in `reduced`; the cut and the deployment stated."""
    config = harness.load_json("configs", CONFIG + ".json")
    published = {"hidden_size": 2688, "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712,
                 "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "mamba_num_heads": 64,
                 "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
                 "conv_kernel": 4, "chunk_size": 128,
                 "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
                 "num_hidden_layers": 52, "layer_norm_epsilon": 1e-5,
                 "max_position_embeddings": 262144}
    for key, value in published.items():
        assert config[key] == value, key
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 52 and [pattern.count(c) for c in "ME*"] == \
        [23, 23, 6]
    assert [i for i, c in enumerate(pattern) if c == "*"] == \
        [5, 12, 19, 26, 33, 42]
    assert config["reduced"] == ["n_routed_experts", "vocab_size"]
    assert (config["n_routed_experts"], config["vocab_size"]) == (16, 16384)
    assert config["published"] == {"n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert config["assumed"]["state_dtype"] == "float32"
    assert config["assumed"]["positions"] == "none"
    assert {"rope_theta", "partial_rotary_factor",
            "max_position_embeddings"} <= set(config["assumed"]["unused"])
    assert "v5e-8" in config["deployment"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key


def test_new_metric_files_are_named_in_the_benchmark():
    with open(os.path.join(os.path.dirname(harness.BENCH),
                           "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    assert cells[CELL]["chips"] == 1
    assert cells[CELL]["traffic"] == "reasoning"
    assert cells[CELL]["config"] == CONFIG
    # (no count of the cells: the next cell must not break this file, as
    # this one broke test_qwen3_next_cpu.py's - PERF.md section 7 a)
    assert len(cells) <= 24
    by_name = {m["name"]: m for m in bm["per_layer"]}
    reports = {m["name"] for m in bm["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    # (not completed tokens/s: five of six sets of six seeds spread over
    # half the bound, 0.5 % - PERF.md section 2, PR 61)
    assert reports == {"serve_itl_p95_ms", "setup_s"}
    for name in NEW:
        spec = harness.load_json("layer_metrics", name + ".json")
        assert CELL in by_name[name]["workloads"]
        assert spec["moves"] == by_name[name]["moves"] in reports
    for m in bm["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in reports, m["name"]
    mix = harness.load_json("traffic", "reasoning.json")
    assert mix["generator"] == "poisson_lengths"
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        [64, 512], [512, 2048])
    assert mix["max_total_tokens"] == 2560
    serve = harness.load_json("workloads", CELL + ".json")["serve"]
    assert serve["max_seq_len"] == 2560 and 32 <= serve["max_batch"] <= 48
    assert serve["num_blocks"] == serve["max_batch"] * 2560 // 16 + 1
    assert serve["prefill_chunk"] == 512 and not serve["prefix_cache"]
