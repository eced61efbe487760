"""Rehearsal of the Qwen3-Next serving cell on the CPU at toy size (the
real runner, generator, family module, reference and the new per-layer
metrics' files), and the family's arithmetic against a parameter tree's
real counts and counts worked out by hand.  Nothing here is a device
number."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks.tests import toy

QWEN = {"family": "qwen3_next", "hidden_size": 32, "intermediate_size": 80,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "partial_rotary_factor": 0.25, "rope_theta": 10000.0,
        "rope_scaling": None, "full_attention_interval": 4,
        "num_hidden_layers": 8, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "num_experts": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "hidden_act": "silu", "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 128, "max_position_embeddings": 512,
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "published": {"num_hidden_layers": 48, "num_experts": 16,
                      "vocab_size": 1024},
        "held": {"layers": list(range(8)), "first_expert": 4,
                 "first_vocab_row": 0},
        "assumed": {"scan_chunk": 4,
                    "init": {"std": 0.2, "router_std": 0.2,
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
CELL = "qwen3-next-80b-a3b-d12.serve.longchat"
CONFIG = "qwen3-next-80b-a3b-d12"
STATE = 32 * 128 * 128 * 4 + 3 * 8192 * 2      # a delta layer's, a slot
NEW = ("gdn_moe_decode_hbm_roofline.serve", "gdn_state_mb_per_step.serve",
       "gdn_live_state_mb_per_step.serve")


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


@pytest.mark.parametrize("trace", [False, True])
def test_qwen3_next_serving_cell_runs_and_matches_its_reference(tmp_path,
                                                                trace):
    from benchmarks.runners import serve_agree

    cell = toy.cell(QWEN, SERVE, RATE, tmp=tmp_path, trace=trace,
                    seconds=1.0)
    run = serve_agree.run(cell)
    load, check = run.notes
    assert check["top1_agreement"] >= check["top1_agreement_floor"] == 0.7
    assert run.correct and run.failed == 0, run.notes
    assert load["compiles_in_window"] == 0
    assert check["requests"] == 6
    steps = run.counters["serve.decode_steps"]
    # the two full layers' rows: every cached position of each
    assert run.counters["serve.attn.rows_read"]["bytes"] >= \
        2 * 3 * run.counters["serve.attn.rows_read"]["calls"]
    assert run.counters["serve.gdn.state_resets"]["calls"] == \
        load["requests"]
    assert "serve.ssm.state_bytes" not in run.counters
    # what the program streams: all 4 slots' state in 6 delta layers, in
    # and out (the toy's sizes); what the live slots need: the published
    # layer's bytes for every live slot and layer, in and out
    toy_state = 4 * 8 * 8 * 4 + 3 * 64 * 2
    assert _read("gdn_state_mb_per_step.serve", cell, run) == \
        pytest.approx(2 * 4 * 6 * toy_state / 1e6)
    live = _read("gdn_live_state_mb_per_step.serve", cell, run)
    assert live == pytest.approx(
        2 * STATE / 1e6 * 6 * steps["bytes"] / steps["calls"])
    # 4 of the router's 16 experts are held: at most 4 touched a layer
    touched = run.counters["serve.moe.experts_touched"]
    assert 0 < touched["bytes"] <= 4 * touched["calls"]
    # the roofline share needs a device trace: nothing to read here
    assert _read("gdn_moe_decode_hbm_roofline.serve", cell, run) is None


def test_the_arithmetic_counts_a_parameter_tree(tmp_path):
    """`held_params` and the per-piece counts against the leaves of the
    tree `build` makes, at the toy widths and at the published ones
    (shapes only)."""
    import jax

    family = harness.plugin("models", "qwen3_next")
    for config in (QWEN, harness.load_json("configs", CONFIG + ".json")):
        model = family.build(config, seq_len=64, n_dev=1)
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        size = lambda t: sum(a.size for a in jax.tree_util.tree_leaves(t))
        assert size(tree) == family.held_params(config)
        blocks = tree["blocks"]
        assert size(blocks[0]["gdn"]) == family.mixer_params(config)
        assert size(blocks[3]["attn"]) == family.attention_params(config)
        mlp = blocks[0]["mlp"]
        assert size(mlp["experts"]) == \
            config["num_experts"] * family.expert_params(config)
        assert size(mlp["shared"]) + size(mlp["shared_gate"]) == \
            family.shared_params(config)
        state, conv = model.layer_spec().state_shapes
        assert 4 * size(jax.ShapeDtypeStruct(state[0], "float32")) + \
            2 * size(jax.ShapeDtypeStruct(conv[0], "bfloat16")) == \
            family.state_bytes(config)


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "qwen3_next")
    config = harness.load_json("configs", CONFIG + ".json")
    # W_qkvz 2048 x 12,288, W_ba 2048 x 64, 4 taps over 8,192 channels,
    # W_o 4096 x 2048, A_log and dt_bias of 32, a gain of 128
    assert family.mixer_params(config) == 25_165_824 + 131_072 + 32_768 \
        + 8_388_608 + 64 + 128 == 33_718_464
    # W_q 2048 x 8192, W_k and W_v 2048 x 512, W_o 4096 x 2048, two norms
    assert family.attention_params(config) == 16_777_216 + 2 * 1_048_576 \
        + 8_388_608 + 512 == 27_263_488
    assert family.expert_params(config) == 3_145_728
    assert family.shared_params(config) == 3_147_776
    delta = 33_718_464 + 3_147_776 + 1_048_576 + 4096 + 64 * 3_145_728
    full = 27_263_488 + 3_147_776 + 1_048_576 + 4096 + 64 * 3_145_728
    assert (delta, full) == (239_245_504, 232_790_528)
    assert family.held_params(config) == 9 * delta + 3 * full \
        + 77_793_280 == 2_929_374_400
    assert round(2 * family.held_params(config) / 1e9, 2) == 5.86
    assert family.fixed_params(config) == 2_929_374_400 \
        - 12 * 64 * 3_145_728 - 18_992 * 2048 == 474_559_680
    assert family.active_params(config) == 474_559_680 \
        + 12 * 10 * 64 / 512 * 3_145_728
    # a slot's state in a delta layer, a token's rows in the full layers
    assert family.state_bytes(config) == 2_097_152 + 49_152 == STATE
    assert 3 * family.row_bytes(config) == 6144
    assert round(48 * 9 * STATE / 1e9, 2) == 0.93
    assert round(39_937 * 16 * 6144 / 1e9, 2) == 3.93
    rows = 3 * 30 * 6000
    flops, nbytes = family.decode_step_cost(config, rows_read=rows,
                                            batch=30, experts_touched=29)
    # the fixed weights (0.95 GB), 29 touched experts in 12 layers
    # (2.19 GB), 30 live slots' state in and out in 9 layers (1.16 GB),
    # the rows read and 30 x 3 written, 2,048 B each
    assert nbytes == 2 * (474_559_680 + 12 * 29 * 3_145_728) \
        + 2 * 30 * 9 * STATE + (rows + 90) * 2048 == 5_403_654_528
    assert flops == 30 * (2 * family.active_params(config)
                          + 9 * 7 * 32 * 128 * 128) + 4 * 16 * 256 * rows
    assert flops / 197e12 < nbytes / 819e9             # HBM-bound
    assert 0.0060 < nbytes / 819e9 < 0.0070
    # no slot live, no expert touched: the fixed weights and a row
    assert family.decode_step_cost(config, 1, 0, 0)[1] == \
        2 * 474_559_680 + 2048
    pflops, pbytes = family.prefill_chunk_cost(config, chunk=512,
                                               rows_read=3 * 6000)
    assert pbytes == 2 * (2_929_374_400 - 18_992 * 2048) + 2 * 9 * STATE \
        + (6000 + 512) * 3 * 2048
    assert family.gdn_step_cost(config, 30) == (
        7 * 30 * 32 * 128 * 128, 8 * 30 * 32 * 128 * 128)
    assert family.prompt_vocab(config) == 18_992


def test_the_configuration_keeps_the_catalogs_widths():
    """Every number of the catalog's `config` under the same key, but the
    three in `reduced`; the cut and the deployment stated."""
    config = harness.load_json("configs", CONFIG + ".json")
    published = {"hidden_size": 2048, "moe_intermediate_size": 512,
                 "shared_expert_intermediate_size": 512,
                 "num_attention_heads": 16, "num_key_value_heads": 2,
                 "head_dim": 256, "linear_num_key_heads": 16,
                 "linear_num_value_heads": 32, "linear_key_head_dim": 128,
                 "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
                 "num_experts_per_tok": 10, "partial_rotary_factor": 0.25,
                 "full_attention_interval": 4, "rope_theta": 10000000,
                 "rms_norm_eps": 1e-6, "intermediate_size": 5120,
                 "max_position_embeddings": 262144}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (12, 64, 18992)
    assert config["published"]["num_experts"] == 512
    assert 8 * config["vocab_size"] == config["published"]["vocab_size"]
    assert config["held"]["layers"] == list(range(12))
    assert config["assumed"]["state_dtype"] == "float32"
    assert "v5e-32" in config["deployment"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key


def test_new_metric_files_are_named_in_the_benchmark():
    with open(os.path.join(os.path.dirname(harness.BENCH),
                           "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    assert cells[CELL]["chips"] == 1 and cells[CELL]["traffic"] == "longchat"
    assert cells[CELL]["config"] == CONFIG
    assert len(cells) == 9 and not any(w["chips"] == 4 for w in bm["workloads"])
    by_name = {m["name"]: m for m in bm["per_layer"]}
    reports = {m["name"] for m in bm["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "serve_itl_p95_ms", "setup_s"} <= reports
    assert "serve_ttft_p95_ms" not in reports
    for name in NEW:
        spec = harness.load_json("layer_metrics", name + ".json")
        assert by_name[name]["workloads"] == [CELL]
        assert spec["moves"] == by_name[name]["moves"] in reports
    for m in bm["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in reports, m["name"]
    mix = harness.load_json("traffic", "longchat.json")
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        [1024, 12288], [128, 1024])
    assert (mix["max_total_tokens"], mix["shape_seed"]) == (13312, 20261004)
    serve = harness.load_json("workloads", CELL + ".json")["serve"]
    assert serve["max_seq_len"] == 13312 and 32 <= serve["max_batch"] <= 48
    assert serve["num_blocks"] == serve["max_batch"] * 13312 // 16 + 1
    assert serve["prefill_chunk"] % 64 == 0 and not serve["prefix_cache"]
