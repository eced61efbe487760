"""Rehearsal of the LFM2-MoE serving cell on the CPU at toy size (the
real runner, generator, family module, reference and the new per-layer
metrics' files), and the family's arithmetic against a parameter tree's
real counts and counts worked out by hand.  Nothing here is a device
number."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks.tests import toy

LFM2 = {"family": "lfm2_moe", "hidden_size": 32,
        "layer_types": ["conv", "conv", "full_attention", "conv"] * 2,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "num_dense_layers": 2, "num_experts": 4, "num_experts_per_tok": 3,
        "routed_scaling_factor": 1, "norm_topk_prob": True,
        "use_expert_bias": True, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
        "vocab_size": 128, "max_position_embeddings": 512,
        "reduced": ["num_experts", "vocab_size"],
        "published": {"num_experts": 16, "vocab_size": 1024},
        "held": {"first_expert": 4, "first_vocab_row": 0},
        "assumed": {"head_dim": 16, "tie_word_embeddings": True,
                    "renorm_eps": 1e-6,
                    "init": {"std": 0.2, "qk_scale": 4.0,
                             "bias_std": 0.05}}}
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
CELL = "lfm2-24b-a2b-e8.serve.assist"
CONFIG = "lfm2-24b-a2b-e8"
NEW = ("conv_moe_decode_hbm_roofline.serve", "conv_state_kb_per_step.serve")


def _benchmark():
    with open(os.path.join(os.path.dirname(harness.BENCH),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


@pytest.mark.parametrize("trace", [False, True])
def test_lfm2_moe_serving_cell_runs_and_matches_its_reference(tmp_path,
                                                              trace):
    from benchmarks.runners import serve_agree

    cell = toy.cell(LFM2, SERVE, RATE, tmp=tmp_path, trace=trace,
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
    assert run.counters["serve.conv.state_resets"]["calls"] == \
        load["requests"]
    assert run.counters["serve.conv.slots_live"]["bytes"] == \
        6 * steps["bytes"]
    # what the program streams: all 4 slots' two rows of 32 at bf16 in
    # the 6 convolution layers, in and out
    assert _read("conv_state_kb_per_step.serve", cell, run) == \
        pytest.approx(2 * 4 * 6 * 2 * 32 * 2 / 1e3)
    # 4 of the router's 16 experts are held, in the 6 routed layers: at
    # most 4 touched a layer
    touched = run.counters["serve.moe.experts_touched"]
    assert touched["calls"] == 6 * steps["calls"]
    assert 0 < touched["bytes"] <= 4 * touched["calls"]
    # the share needs a device trace: nothing to read here
    assert _read("conv_moe_decode_hbm_roofline.serve", cell, run) is None


def test_the_arithmetic_counts_a_parameter_tree(tmp_path):
    """`held_params` and the per-piece counts against the leaves of the
    tree `build` makes, at the toy widths and at the published ones
    (shapes only)."""
    import jax

    family = harness.plugin("models", "lfm2_moe")
    for config in (LFM2, harness.load_json("configs", CONFIG + ".json")):
        model = family.build(config, seq_len=64, n_dev=1)
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        size = lambda t: sum(a.size for a in jax.tree_util.tree_leaves(t))
        assert size(tree) == family.held_params(config)
        blocks = tree["blocks"]
        assert size(blocks[0]["conv"]) == family.conv_params(config)
        assert size(blocks[2]["attn"]) == family.attention_params(config)
        mlp = blocks[2]["mlp"]
        assert sorted(mlp) == ["experts", "router", "select_bias"]
        assert sorted(mlp["experts"]) == ["down", "gate", "up"]
        assert size(mlp["experts"]) == \
            config["num_experts"] * family.expert_params(config)
        assert sorted(blocks[1]["mlp"]) == ["down", "gate", "up"]
        (rows, dtype), = model.layer_spec().state_shapes
        assert dtype is None and 2 * size(
            jax.ShapeDtypeStruct(rows, "bfloat16")) == \
            family.state_bytes(config)


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "lfm2_moe")
    config = harness.load_json("configs", CONFIG + ".json")
    # W_in 2048 x 6144, 3 taps over 2,048 channels, W_out 2048 x 2048
    assert family.conv_params(config) == 12_582_912 + 6144 + 4_194_304 \
        == 16_783_360
    # W_q 2048 x 2048, W_k and W_v 2048 x 512, W_o, two gains of 64
    assert family.attention_params(config) == 2 * 4_194_304 \
        + 2 * 1_048_576 + 128 == 10_485_888
    assert family.expert_params(config) == 3 * 2048 * 1536 == 9_437_184
    mixers = 30 * 16_783_360 + 10 * 10_485_888 + 40 * 4096
    assert mixers == 608_523_520
    assert family.held_params(config) == mixers + 2 * 72_351_744 \
        + 38 * (131_136 + 8 * 9_437_184) + 16_779_264 == 3_643_893_376
    assert round(2 * family.held_params(config) / 1e9, 2) == 7.29
    # the whole model: all 64 experts, all 65,536 rows
    assert mixers + 2 * 72_351_744 + 38 * (131_136 + 64 * 9_437_184) \
        + 134_219_776 == 23_843_661_440
    fixed = 3_643_893_376 - 38 * 8 * 9_437_184
    assert family.fixed_params(config) == fixed == 774_989_440
    assert family.active_params(config) == fixed \
        + 38 * 4 * 8 / 64 * 9_437_184 == 954_295_936
    # a slot's rows in a convolution layer, a token's in the ten layers
    assert family.state_bytes(config) == 8192
    assert 30 * family.state_bytes(config) == 245_760
    assert 10 * family.row_bytes(config) == 20_480
    assert round(18_433 * 16 * 20_480 / 1e9, 2) == 6.04
    rows = 10 * 64 * 1080
    flops, nbytes = family.decode_step_cost(config, rows_read=rows,
                                            batch=64, experts_touched=7.9)
    # the fixed weights (1.55 GB), 7.9 touched experts of three matrices
    # in 38 layers (5.67 GB), 64 live slots' two rows in and out in 30
    # layers (0.03 GB), the rows read and 64 x 10 written, 2,048 B each
    assert nbytes == pytest.approx(
        2 * (fixed + 38 * 7.9 * 9_437_184) + 2 * 64 * 245_760
        + (rows + 640) * 2048)
    assert 8.6e9 < nbytes < 8.7e9
    assert flops == 64 * (2 * 954_295_936 + 30 * 8 * 2048) \
        + 4 * 32 * 64 * rows
    assert flops / 197e12 < nbytes / 819e9             # HBM-bound
    assert 0.0105 < nbytes / 819e9 < 0.0107
    # no slot live, no expert touched: the fixed weights and a row
    assert family.decode_step_cost(config, 1, 0, 0)[1] == 2 * fixed + 2048
    assert family.prompt_vocab(config) == 8192


def test_the_configuration_keeps_the_catalogs_widths():
    """Every number of the catalog's `config` under the same key, but the
    two in `reduced`; the cut and the deployment stated."""
    config = harness.load_json("configs", CONFIG + ".json")
    published = {"hidden_size": 2048, "intermediate_size": 11776,
                 "moe_intermediate_size": 1536, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "conv_L_cache": 3,
                 "num_experts_per_tok": 4, "num_dense_layers": 2,
                 "routed_scaling_factor": 1, "num_hidden_layers": 40,
                 "norm_eps": 1e-5, "max_position_embeddings": 128000}
    for key, value in published.items():
        assert config[key] == value, key
    types = config["layer_types"]
    assert len(types) == 40
    assert [i for i, t in enumerate(types) if t == "full_attention"] == \
        list(range(2, 40, 4))
    assert set(types) == {"conv", "full_attention"}
    assert config["reduced"] == ["num_experts", "vocab_size"]
    assert (config["num_experts"], config["vocab_size"]) == (8, 8192)
    assert config["published"] == {"num_experts": 64, "vocab_size": 65536}
    assumed = config["assumed"]
    assert assumed["head_dim"] == 64 and assumed["tie_word_embeddings"]
    assert assumed["renorm_eps"] == 1e-6
    assert "max_position_embeddings" in assumed["unused"]
    assert "v5e-8" in config["deployment"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key


def test_new_metric_files_are_named_in_the_benchmark():
    """Cells, files and lists counted from BENCHMARK.json, not by a
    literal: the next cell must not break this file."""
    bm = _benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    assert cells[CELL]["chips"] == 1
    assert cells[CELL]["traffic"] == "assist"
    assert cells[CELL]["config"] == CONFIG
    assert len(cells) <= 24
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= \
        max(1, len(cells) // 4)
    configs = {c["name"]: c for c in bm["configs"]}
    assert configs[CONFIG]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == harness.load_json(
        "configs", CONFIG + ".json")["reduced"]
    # every configuration is some cell's, every file there is
    assert {w["config"] for w in bm["workloads"]} == set(configs)
    for kind, names in (("workloads", cells), ("configs", configs)):
        for name in names:
            assert os.path.exists(os.path.join(
                harness.BENCH, kind, name + ".json")), (kind, name)
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name, m in by_name.items():
        assert os.path.exists(os.path.join(
            harness.BENCH, "layer_metrics", name + ".json")), name
        assert "workloads" in m, name
    reports = {m["name"] for m in bm["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert "setup_s" in reports and len(reports) >= 2
    assert "serve_ttft_p95_ms" not in reports
    for name in NEW:
        spec = harness.load_json("layer_metrics", name + ".json")
        assert by_name[name]["workloads"] == [CELL]
        assert spec["moves"] == by_name[name]["moves"] in reports
        for key in ("unit", "better", "source", "layer"):
            assert spec[key] == by_name[name][key], (name, key)
    for m in bm["per_layer"]:
        if CELL in m["workloads"]:
            assert m["moves"] in reports, m["name"]
    mix = harness.load_json("traffic", "assist.json")
    assert mix["generator"] == "poisson_lengths"
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        [256, 2048], [128, 1024])
    assert mix["max_total_tokens"] == 3072
    assert mix["shape_seed"] == 20261063
    workload = harness.load_json("workloads", CELL + ".json")
    serve = workload["serve"]
    assert workload["runner"] == "serve_agree"
    assert serve["max_seq_len"] == 3072 and serve["max_batch"] in (80, 96)
    assert serve["num_blocks"] == serve["max_batch"] * 3072 // 16 + 1
    assert serve["prefill_chunk"] == 512 and not serve["prefix_cache"]
    assert workload["check"]["requests"] == 4
