"""Rehearsal of the Command A+ serving cell on the CPU at toy size (the
real runner, generator, family module, reference and the new per-layer
metrics' files), and the family's arithmetic against counts worked out by
hand.  Nothing here is a device number."""

import json

import pytest

from benchmarks import harness
from benchmarks.tests import toy

TYPES = ["sliding_attention"] * 3 + ["full_attention"]
COHERE = {"family": "cohere2_moe", "hidden_size": 64,
          "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
          "intermediate_size": 32, "num_experts": 4,
          "num_experts_per_tok": 4, "num_shared_experts": 2,
          "num_hidden_layers": 8, "layer_types": TYPES * 2,
          "layer_switch": 4, "sliding_window": 32, "vocab_size": 128,
          "layer_norm_eps": 1e-5, "rope_theta": 50000,
          "max_position_embeddings": 512, "attention_bias": False,
          "hidden_act": "silu", "use_gated_activation": True,
          "use_qk_norm": False, "use_parallel_block": True,
          "use_parallel_embedding": False,
          "position_embedding_type": "rope_gptj", "rotary_pct": 1,
          "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
          "order_of_interleaved_layers": "local_attn_first",
          "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
          "shared_expert_combination_strategy": "average",
          "first_k_dense_replace": 0, "tie_word_embeddings": True,
          "logit_scale": 1, "reduced": ["num_experts"],
          "published": {"num_experts": 8}, "held": {"first_expert": 4},
          "assumed": {"init": {"std": 0.2}}}
SERVE = {"runner": "serve_agree",
         "serve": {"block_size": 4, "num_blocks": 129, "max_batch": 4,
                   "prefill_chunk": 16, "max_seq_len": 256,
                   "prefix_cache": False},
         "model": {"param_dtype": "bfloat16"}, "drain_seconds": 30,
         "check": {"requests": 6, "batch": 1, "logit_margin": 0.4,
                   "top1_agreement_floor": 0.8},
         "trace": {"seconds": 0.3}}
MIXED = {"generator": "poisson_lengths", "rate_rps": 12.0,
         "prompt_tokens": [8, 200], "output_tokens": [4, 20],
         "max_total_tokens": 256, "shape_seed": 7}
CELL = "command-a-plus-d4.serve.mixedlen"


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


@pytest.mark.parametrize("trace", [False, True])
def test_cohere_serving_cell_runs_and_matches_its_reference(tmp_path, trace):
    from benchmarks.runners import serve_agree

    cell = toy.cell(COHERE, SERVE, MIXED, tmp=tmp_path, trace=trace,
                    seconds=1.0)
    run = serve_agree.run(cell)
    load, check = run.notes
    assert check["top1_agreement"] >= check["top1_agreement_floor"] == 0.8
    assert run.correct and run.failed == 0, run.notes
    assert load["compiles_in_window"] == 0
    assert check["requests"] == 6
    window = _read("window_rows_per_query.serve", cell, run)
    rows = _read("attn_rows_per_query.serve", cell, run)
    touched = _read("moe_experts_touched_per_layer.serve", cell, run)
    # 6 sliding layers of at most 32 rows, 2 full ones of every row
    assert 8 <= window <= 32 and rows >= 8 * window
    assert run.counters["kv.ring_wraps"]["calls"] >= 1
    assert 0 < touched <= 4            # of the 4 held of 8, up to 4 slots
    assert "serve.moe.assignments" not in run.counters  # behind a share
    # the roofline share needs a device trace: nothing to read here
    assert _read("swa_moe_decode_hbm_roofline.serve", cell, run) is None


def test_the_agreement_floor_can_only_turn_correct_false(monkeypatch):
    """`serve_agree` adds one comparison to `serve.run`'s result."""
    from benchmarks.runners import serve, serve_agree

    def result(ok, agreement):
        return harness.RunResult(
            end_to_end={}, correct=ok, attempted=1, failed=0,
            notes=[{}, {"top1_agreement": agreement}], memory_peak_bytes=0)

    class Cell:
        workload = {"check": {"top1_agreement_floor": 0.97}}

    for ok, agreement, want in ((True, 0.989, True), (True, 0.887, False),
                                (False, 0.989, False), (True, 0.97, True)):
        monkeypatch.setattr(serve, "run", lambda cell: result(ok, agreement))
        got = serve_agree.run(Cell())
        assert got.correct is want
        assert got.notes[-1] == {"top1_agreement": agreement,
                                 "top1_agreement_floor": 0.97}


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "cohere2_moe")
    config = harness.load_json("configs", "command-a-plus-d4.json")
    # attention: W_q and W_o 4096 x 16,384 each, W_k and W_v 4096 x 1,024
    assert family.attention_params(config) == \
        2 * 67_108_864 + 2 * 4_194_304 == 142_606_336
    assert family.expert_params(config) == 3 * 4096 * 4096 == 50_331_648
    # a layer outside its routed experts: attention, four shared, router
    outside = 142_606_336 + 4 * 50_331_648 + 4096 * 128
    assert outside == 344_457_216
    layer = outside + 16 * 50_331_648
    assert layer == 1_149_763_584                       # 2.30 GB in bf16
    wte = 4096 * 32768
    assert family.fixed_params(config) == 4 * outside + wte == 1_512_046_592
    assert family.held_params(config) == 4 * layer + wte == 4_733_272_064
    assert round(2 * family.held_params(config) / 1e9, 2) == 9.47
    # of a token's 8 experts one in eight lies here, on average
    assert family.active_params(config) == \
        1_512_046_592 + 4 * 50_331_648 == 1_713_373_184
    # a row of a layer: 8 keys and 8 values of 128, bf16
    assert family.row_bytes(config) == 4096
    assert family.layer_windows(config) == [4096, 4096, 4096, 0]
    assert family.attended_rows(config, 1000) == 4000
    assert family.attended_rows(config, 15000) == 3 * 4096 + 15000
    rows = 12 * family.attended_rows(config, 6000)
    flops, nbytes = family.decode_step_cost(
        config, rows_read=rows, batch=12, experts_touched=8)
    # the fixed weights (3.02 GB), 8 experts of 100.7 MB in 4 layers,
    # the rows read and 12 x 4 written, 4,096 B each
    assert nbytes == 2 * 1_512_046_592 + 4 * 8 * 100_663_296 \
        + (rows + 48) * 4096 == 7_144_407_040
    assert flops == 2 * 1_713_373_184 * 12 + 4 * 128 * 128 * rows
    assert flops / 197e12 < nbytes / 819e9     # HBM-bound
    assert 0.0075 < nbytes / 819e9 < 0.0090    # the issue's floor, ~7.5 ms
    none = family.decode_step_cost(config, 1, 1, 0)[1]
    assert none == 2 * 1_512_046_592 + 5 * 4096
    whole = family.attended_rows(config, 16384)
    pflops, pbytes = family.prefill_chunk_cost(config, chunk=512,
                                               rows_read=whole)
    # 512 x 8 / 8 assignments can touch all 16 held experts
    assert pbytes == 2 * (1_512_046_592 + 4 * 16 * 50_331_648) \
        + (whole + 4 * 256 + 4 * 512) * 4096
    # ~1.75 TFLOP of products and ~0.94 of scores and weighted sums
    assert 1.7e12 < 2 * family.active_params(config) * 512 < 1.8e12
    assert 0.9e12 < pflops - 2 * family.active_params(config) * 512 < 1.0e12
    # 6 per parameter met and the rows a causal query attends on average
    assert family.model_flops_per_token(config, 16384) == \
        6 * 1_713_373_184 + 12 * 128 * 128 * (3 * 4096 + 8192)


def test_build_gives_the_published_widths_and_refuses_the_rest():
    family = harness.plugin("models", "cohere2_moe")
    config = harness.load_json("configs", "command-a-plus-d4.json")
    model = family.build(config, seq_len=16384, n_dev=1,
                         param_dtype="bfloat16")
    c = model.config
    assert (c.num_layers, c.d_model, c.num_heads, c.kv_heads, c.head_dim,
            c.d_expert, c.num_experts, c.top_k, c.num_shared, c.experts_held,
            c.first_expert, c.window, c.period, c.vocab_size) == \
        (4, 4096, 128, 8, 128, 4096, 128, 8, 4, 16, 0, 4096, 4, 32768)
    assert c.rope_theta == 50000.0 and c.layer_norm_eps == 1e-5
    spec = model.layer_spec()
    assert spec.layer_windows == (4096, 4096, 4096, 0) and \
        spec.held == (0, 16)
    assert family.prompt_vocab(config) == 32768
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(config, seq_len=1 << 20, n_dev=1)
    with pytest.raises(ValueError, match="all-to-all"):
        family.build(config, seq_len=4096, n_dev=4)
    for key, other in (("use_qk_norm", True), ("use_parallel_block", False),
                       ("expert_selection_fn", "softmax"),
                       ("norm_topk_prob", False), ("first_k_dense_replace", 1),
                       ("shared_expert_combination_strategy", "sum"),
                       ("position_embedding_type", "rope_neox"),
                       ("tie_word_embeddings", False), ("logit_scale", 0.25)):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: other}), seq_len=4096, n_dev=1)
    with pytest.raises(ValueError, match="layer_types"):
        family.build(dict(config, layer_types=["full_attention"] * 32),
                     seq_len=4096, n_dev=1)


def test_the_configuration_file_keeps_every_published_size():
    config = harness.load_json("configs", "command-a-plus-d4.json")
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(json.loads(line) for line in f
                       if '"name": "command-a-plus-05-2026"' in line)
    except OSError:
        pytest.skip("the catalog is not on this machine")
    differ = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(differ) == sorted(config["reduced"]) and config["reduced"] \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "num_experts": 128, "vocab_size": 262144}
    assert config["source"] == row["source_url"]
    assert config["vocab_size"] * 8 == 262144 and config["num_experts"] >= 8
    assert all(k + "_why" in config["assumed"] for k in config["assumed"]
               if not k.endswith("_why"))
    assert "64 v5e chips" in config["deployment"]


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
        cell["serve"], block_size=16, num_blocks=16385, max_batch=16,
        max_seq_len=16384, admission="continuous", prefix_cache=False,
        kv_dtype="bf16")
    assert cell["serve"]["prefill_chunk"] in (256, 512)
    assert cell["model"] == {"param_dtype": "bfloat16"}
    for key in ("serve_why", "drain_why", "why"):
        assert len(cell[key]) > 40
    assert len(cell["check"]["why"]) > 40 and cell["check"]["requests"] >= 8
    mix = harness.load_json("traffic", entry["traffic"] + ".json")
    assert mix["generator"] == "poisson_lengths"
    assert mix["prompt_tokens"] == [256, 15360]
    assert mix["output_tokens"] == [64, 512]
    assert mix["shape_seed"] == 20260930 and mix["max_total_tokens"] == 16384
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in ("window_rows_per_query.serve", "attn_rows_per_query.serve",
                 "swa_moe_decode_hbm_roofline.serve"):
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_itl_p95_ms"
    # the first-token tail is not admissible at 40 requests (PERF.md
    # section 2, PR 44): the cell reports the other two
    for name, listed in (("serve_ttft_p95_ms", False),
                         ("serve_itl_p95_ms", True),
                         ("serve_tokens_per_s", True)):
        assert (CELL in next(m for m in bm["end_to_end"]
                             if m["name"] == name)["workloads"]) is listed
    assert cell["runner"] == "serve_agree" and mix["rate_rps"] == 0.8
    assert cell["check"]["top1_agreement_floor"] == 0.97
