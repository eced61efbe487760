"""Rehearsal of the Granite 4.0-H serving cell on the CPU at toy size (the
real runner, generator, family module, reference and the new per-layer
metrics' files), and the family's arithmetic against counts worked out by
hand.  Nothing here is a device number."""

import json

import pytest

from benchmarks import harness
from benchmarks.tests import toy

TYPES = ["mamba", "attention", "mamba"]
GRANITE = {"family": "granite_hybrid", "hidden_size": 32,
           "intermediate_size": 64, "shared_intermediate_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 6, "layer_types": TYPES * 2,
           "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
           "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
           "mamba_chunk_size": 4, "mamba_conv_bias": True,
           "mamba_proj_bias": False, "attention_bias": False,
           "position_embedding_type": "nope", "hidden_act": "silu",
           "normalization_function": "rmsnorm", "rms_norm_eps": 1e-5,
           "tie_word_embeddings": True, "num_local_experts": 0,
           "num_experts_per_tok": 0, "vocab_size": 128,
           "max_position_embeddings": 512, "embedding_multiplier": 12,
           "residual_multiplier": 0.22, "attention_multiplier": 0.125,
           "logits_scaling": 8, "reduced": [],
           "assumed": {"init": {"std": 0.2, "A": [1.0, 16.0],
                                "dt": [0.001, 0.1]}}}
SERVE = {"runner": "serve_agree",
         "serve": {"block_size": 4, "num_blocks": 129, "max_batch": 4,
                   "prefill_chunk": 8, "max_seq_len": 128,
                   "prefix_cache": False},
         "model": {"param_dtype": "bfloat16"}, "drain_seconds": 30,
         "check": {"requests": 6, "batch": 1, "logit_margin": 0.01,
                   "top1_agreement_floor": 0.8},
         "trace": {"seconds": 0.3}}
RATE = {"generator": "poisson_lengths", "rate_rps": 12.0,
        "prompt_tokens": [2, 60], "output_tokens": [4, 20],
        "max_total_tokens": 128, "shape_seed": 7}
CELL = "granite-4.0-h-micro.serve.chatrate"
STATE = 64 * 64 * 128 * 4 + 3 * 4352 * 2       # a layer's, a slot


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


@pytest.mark.parametrize("trace", [False, True])
def test_granite_serving_cell_runs_and_matches_its_reference(tmp_path,
                                                             trace):
    from benchmarks.runners import serve_agree

    cell = toy.cell(GRANITE, SERVE, RATE, tmp=tmp_path, trace=trace,
                    seconds=1.0)
    run = serve_agree.run(cell)
    load, check = run.notes
    assert check["top1_agreement"] >= check["top1_agreement_floor"] == 0.8
    assert run.correct and run.failed == 0, run.notes
    assert load["compiles_in_window"] == 0
    assert check["requests"] == 6
    steps = run.counters["serve.decode_steps"]
    # the two attention layers' rows: every cached position of each
    assert _read("attn_rows_per_query.serve", cell, run) >= 2 * 3
    assert run.counters["serve.ssm.state_resets"]["calls"] == \
        load["requests"]
    # what the program streams: all 4 slots' state in 4 layers, in and out
    # (the toy's sizes); what the live slots need: the published layer's
    # bytes for every live slot and layer, in and out
    toy_state = 8 * 8 * 16 * 4 + 3 * 96 * 2
    assert _read("ssm_state_mb_per_step.serve", cell, run) == \
        pytest.approx(2 * 4 * 4 * toy_state / 1e6)
    live = _read("ssm_live_state_mb_per_step.serve", cell, run)
    assert live == pytest.approx(
        2 * STATE / 1e6 * 4 * steps["bytes"] / steps["calls"])
    # the roofline share needs a device trace: nothing to read here
    assert _read("ssm_decode_hbm_roofline.serve", cell, run) is None


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "granite_hybrid")
    config = harness.load_json("configs", "granite-4.0-h-micro.json")
    # W_in 2048 x (4096 + 4352 + 64), W_out 4096 x 2048, 4 taps and a
    # bias over 4,352 channels, dt_bias, A_log and D of 64, a gain of 4,096
    assert family.mixer_params(config) == 17_432_576 + 8_388_608 \
        + 21_760 + 192 + 4096 == 25_847_232
    # W_q and W_o 2048 x 2048, W_k and W_v 2048 x 512
    assert family.attention_params(config) == 10_485_760
    assert family.mlp_params(config) == 3 * 2048 * 8192 + 4096 == 50_335_744
    assert family.layer_counts(config) == (36, 4)
    assert 25_847_232 + 50_335_744 == 76_182_976      # a Mamba layer
    assert 10_485_760 + 50_335_744 == 60_821_504      # an attention layer
    assert family.total_params(config) == 36 * 76_182_976 \
        + 4 * 60_821_504 + 100352 * 2048 + 2048 == 3_191_396_096
    assert round(2 * family.total_params(config) / 1e9, 2) == 6.38
    # a slot's state in a layer: 64 x 64 x 128 float32 and 3 rows of
    # 4,352 bf16; a token's rows in an attention layer: 8 K and 8 V of 64
    assert family.state_bytes(config) == 2_097_152 + 26_112 == STATE
    assert family.row_bytes(config) == 2048
    assert 4 * family.row_bytes(config) == 8192       # a token, all layers
    assert round(64 * 36 * STATE / 1e9, 2) == 4.89    # 64 slots
    rows = 4 * 30 * 600
    flops, nbytes = family.decode_step_cost(config, rows_read=rows, batch=30)
    # the weights (6.38 GB), 30 live slots' state in and out in 36 layers
    # (4.59 GB), the rows read and 30 x 4 written, 2,048 B each
    assert nbytes == 2 * 3_191_396_096 + 2 * 30 * 36 * STATE \
        + (rows + 120) * 2048 == 11_116_744_192
    assert flops == 30 * (2 * 3_191_396_096 + 36 * 5 * 64 * 64 * 128) \
        + 4 * 32 * 64 * rows
    assert flops / 197e12 < nbytes / 819e9             # HBM-bound
    assert 0.0130 < nbytes / 819e9 < 0.0140            # the issue's 13.5 ms
    # every slot's state whatever is live: the floor of the step as built
    built = family.decode_step_cost(config, rows_read=rows, batch=64)[1]
    assert 0.0190 < built / 819e9 < 0.0200             # the issue's 19.6
    none = family.decode_step_cost(config, 1, 0)[1]
    assert none == 2 * 3_191_396_096 + 2048
    pflops, pbytes = family.prefill_chunk_cost(config, chunk=512,
                                               rows_read=4 * 512)
    assert pbytes == 2 * 3_191_396_096 + 2 * 36 * STATE \
        + (2048 + 4 * 256 + 4 * 512) * 2048
    # ~3.27 TFLOP of products with the weights, the issue's 3.1
    assert 3.2e12 < 2 * family.total_params(config) * 512 < 3.3e12
    scan = 256 * (128 + 4096) + 4 * 64 * 64 * 128
    assert family.scan_flops_per_token(config, 256) == scan
    assert pflops == 512 * (2 * 3_191_396_096 + 36 * scan) \
        + 4 * 32 * 64 * 512 * 2048
    assert family.model_flops_per_token(config, 2048) == \
        6 * 3_191_396_096 + 12 * 32 * 64 * 4 * 1024 + 3 * 36 * scan


def test_build_gives_the_published_widths_and_refuses_the_rest():
    family = harness.plugin("models", "granite_hybrid")
    config = harness.load_json("configs", "granite-4.0-h-micro.json")
    model = family.build(config, seq_len=2048, n_dev=1,
                         param_dtype="bfloat16")
    c = model.config
    assert (c.num_layers, c.period, c.attention_at, c.d_model, c.d_ffn,
            c.num_heads, c.kv_heads, c.head_dim, c.ssm_heads,
            c.ssm_head_dim, c.ssm_state, c.ssm_conv, c.ssm_chunk,
            c.vocab_size) == (40, 10, (5,), 2048, 8192, 32, 8, 64, 64, 64,
                              128, 4, 256, 100352)
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling, c.rms_norm_eps) == \
        (12.0, 0.22, 0.015625, 8.0, 1e-5)
    assert (c.init_std, c.init_a, c.init_dt) == (0.02, (1.0, 16.0),
                                                 (0.001, 0.1))
    spec = model.layer_spec()
    assert [i for i in range(40) if spec.mixer_of(i) == "attention"] == \
        [5, 15, 25, 35]
    assert family.prompt_vocab(config) == 100352
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(config, seq_len=1 << 20, n_dev=1)
    with pytest.raises(ValueError, match="whole on one chip"):
        family.build(config, seq_len=2048, n_dev=4)
    for key, other in (("num_local_experts", 8), ("mamba_n_groups", 2),
                       ("mamba_proj_bias", True), ("attention_bias", True),
                       ("mamba_conv_bias", False),
                       ("position_embedding_type", "rope"),
                       ("tie_word_embeddings", False),
                       ("normalization_function", "layernorm")):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: other}), seq_len=2048, n_dev=1)
    with pytest.raises(ValueError, match="layer_types"):
        family.build(dict(config, layer_types=["moe"] * 40), seq_len=2048,
                     n_dev=1)
    with pytest.raises(ValueError, match="mamba_expand"):
        family.build(dict(config, mamba_expand=4), seq_len=2048, n_dev=1)


def test_the_configuration_file_keeps_every_published_size():
    config = harness.load_json("configs", "granite-4.0-h-micro.json")
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(json.loads(line) for line in f
                       if '"name": "granite-4.0-h-micro"' in line)
    except OSError:
        pytest.skip("the catalog is not on this machine")
    assert [k for k, v in row["config"].items() if config.get(k) != v] == []
    assert config["reduced"] == [] and config["source"] == row["source_url"]
    assert config["num_hidden_layers"] == 40 and \
        config["vocab_size"] == 100352
    assumed = config["assumed"]
    assert assumed["state_dtype"] == "float32"
    assert assumed["init"] == {
        "std": 0.02, "A": [1.0, 16.0], "dt": [0.001, 0.1],
        "conv": "uniform(-1/sqrt(mamba_d_conv), 1/sqrt(mamba_d_conv))"}
    assert all(k + "_why" in assumed for k in assumed
               if not k.endswith("_why"))
    assert "one v5e chip holds the whole model" in config["deployment"]


def test_the_cell_is_in_the_benchmark_with_its_metrics():
    """Looked up by name: what other cells and metrics the benchmark
    holds, and in which order, is not this test's."""
    with open(harness.BENCH + "/../BENCHMARK.json") as f:
        bm = json.load(f)
    entry = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["config"] == "granite-4.0-h-micro"
    assert entry["traffic"] == "chatrate"
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    assert conf["reduced"] == [] and len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/granite-4.0-h-micro.json"
    cell = harness.load_json("workloads", CELL + ".json")
    assert cell["serve"] == dict(
        cell["serve"], block_size=16, max_seq_len=2048,
        admission="continuous", prefix_cache=False, kv_dtype="bf16")
    assert 48 <= cell["serve"]["max_batch"] <= 64
    assert cell["serve"]["num_blocks"] == \
        cell["serve"]["max_batch"] * 2048 // 16 + 1
    assert cell["serve"]["prefill_chunk"] in (256, 512, 1024)
    assert cell["model"] == {"param_dtype": "bfloat16"}
    for key in ("serve_why", "drain_why", "why"):
        assert len(cell[key]) > 40
    assert len(cell["check"]["why"]) > 40 and cell["check"]["requests"] >= 8
    assert cell["runner"] == "serve_agree"
    assert 0 < cell["check"]["top1_agreement_floor"] < 1
    mix = harness.load_json("traffic", entry["traffic"] + ".json")
    assert mix["generator"] == "poisson_lengths"
    assert mix["prompt_tokens"] == [64, 1024]
    assert mix["output_tokens"] == [64, 512]
    assert mix["max_total_tokens"] == 2048 and mix["rate_rps"] > 1
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in ("ssm_decode_hbm_roofline.serve",
                 "ssm_state_mb_per_step.serve",
                 "ssm_live_state_mb_per_step.serve"):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_itl_p95_ms"
        assert m["layer"] == "serving programs"
        spec = harness.load_json("layer_metrics", name + ".json")
        assert spec["unit"] == m["unit"] and spec["source"] == m["source"]
    for name in ("decode_batch_mean.serve", "decode_step_ms.serve",
                 "device_idle_pct.serve", "decode_ahead_pct.serve",
                 "token_gap_p50_ms.serve", "token_gap_p95_ms.serve",
                 "chunk_gap_share_pct.serve", "host_decode_launch_ms.serve",
                 "host_read_blocked_ms.serve", "host_bookkeep_ms.serve",
                 "idle_no_work_pct.serve", "idle_host_pct.serve",
                 "greedy_steps_pct.serve", "attn_rows_per_query.serve"):
        assert CELL in by_name[name]["workloads"], name
    reported = {m["name"] for m in bm["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    # the first-token tail is not admissible (six runs spread by 9.2 %,
    # PERF.md section 2, PR 46): the cell reports the other two, and no
    # per-layer metric that moves the one it leaves out
    assert reported == {"setup_s", "serve_tokens_per_s", "serve_itl_p95_ms"}
    for m in bm["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in reported, m["name"]
    live = harness.load_json("layer_metrics",
                             "ssm_live_state_mb_per_step.serve.json")
    assert live["args"]["scale"] == pytest.approx(2 * STATE / 1e6)
