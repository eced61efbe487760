"""Rehearsal of the GLM-5.2 serving cell on the CPU at toy size (the
real runner, generator, family module, reference and the new per-layer
metrics' files and reader), and the family's arithmetic against counts
worked out by hand.  Nothing here is a device number."""

import pytest

from benchmarks import harness
from benchmarks.tests import toy

KINDS = ["full"] * 3 + ["shared", "shared", "shared", "full", "shared"]
GLM = {"family": "glm_moe_dsa", "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 16,
       "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
       "v_head_dim": 12, "index_n_heads": 3, "index_head_dim": 8,
       "index_topk": 8, "indexer_types": KINDS,
       "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 5,
       "intermediate_size": 48, "moe_intermediate_size": 24,
       "n_routed_experts": 4, "num_experts_per_tok": 4, "n_shared_experts": 1,
       "num_hidden_layers": 5, "first_k_dense_replace": 1, "vocab_size": 128,
       "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.5,
       "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
       "max_position_embeddings": 512, "attention_bias": False,
       "hidden_act": "silu", "moe_layer_freq": 1, "n_group": 1,
       "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
       "norm_topk_prob": True, "rope_interleave": True,
       "indexer_rope_interleave": True, "tie_word_embeddings": False,
       "num_nextn_predict_layers": 0,
       "reduced": ["num_hidden_layers", "n_routed_experts"],
       "published": {"n_routed_experts": 16},
       "held": {"layers": [2, 3, 4, 5, 6], "first_expert": 4},
       "assumed": {"index_norm_eps": 1e-6,
                   "init": {"std": 0.15, "router_std": 0.5, "bias_std": 0.3,
                            "query_std": 0.6, "embed_std": 0.15}}}
SERVE = {"runner": "serve_agree",
         "serve": {"block_size": 4, "num_blocks": 129, "max_batch": 4,
                   "prefill_chunk": 16, "max_seq_len": 128,
                   "prefix_cache": False},
         "model": {"param_dtype": "bfloat16"}, "drain_seconds": 60,
         "check": {"requests": 4, "batch": 1, "logit_margin": 1.5,
                   "top1_agreement_floor": 0.6},
         "trace": {"seconds": 0.3}}
LONG = {"generator": "poisson_lengths", "rate_rps": 8.0,
        "prompt_tokens": [24, 100], "output_tokens": [4, 16],
        "max_total_tokens": 128, "shape_seed": 7}
CELL = "glm-5.2-d5.serve.longctx"


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


def test_glm_serving_cell_runs_and_matches_its_reference(tmp_path):
    from benchmarks.runners import serve_agree

    cell = toy.cell(GLM, SERVE, LONG, tmp=tmp_path, seconds=1.0)
    run = serve_agree.run(cell)
    load, check = run.notes
    assert run.failed == 0 and load["compiles_in_window"] == 0, run.notes
    assert check["requests"] == 4
    assert check["top1_agreement"] >= check["top1_agreement_floor"], check
    assert run.correct, run.notes
    keys = _read("dsa_keys_scored_per_query.serve", cell, run)
    chosen = _read("dsa_rows_selected_per_query.serve", cell, run)
    fetched = _read("dsa_rows_fetched_per_query.serve", cell, run)
    # every prompt is past index_topk rows: every decoded query scores
    # its 24..128 keys and attends exactly 8 rows a layer
    assert 24 < keys <= 128 and chosen == 8.0 and fetched == 8.0
    assert run.counters["serve.sparse.selections_shared"]["calls"] > 0
    assert 0 < _read("moe_experts_touched_per_layer.serve", cell, run) <= 4
    # the roofline share needs a device trace: nothing to read here
    assert _read("dsa_moe_decode_hbm_roofline.serve", cell, run) is None


def test_the_selected_roofline_reader_hands_both_counts():
    """Stubs of a trace and a run: the reader asks the family's cost
    with the keys scored AND the rows selected a step, each from its
    own counter, and returns None where a counter is missing (a program
    older than the counters)."""
    import types

    reader = harness.plugin("readers", "trace_selected_program_roofline")
    seen = {}

    def cost(config, keys, rows, batch, experts):
        seen.update(keys=keys, rows=rows, batch=batch, experts=experts)
        return 2e9, 5e8

    cell = types.SimpleNamespace(
        family=types.SimpleNamespace(decode_step_cost=cost), config={},
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    trace = types.SimpleNamespace(
        module_durations=lambda name: [0.02, 0.01, 0.03])
    counters = {"serve.decode_steps": {"calls": 10, "bytes": 40},
                "serve.sparse.keys_scored": {"calls": 80, "bytes": 800000},
                "serve.sparse.rows_selected": {"calls": 200,
                                               "bytes": 409600},
                "serve.moe.experts_touched": {"calls": 40, "bytes": 100}}
    args = dict(program="jit_decode", cost="decode_step_cost",
                keys="serve.sparse.keys_scored",
                rows="serve.sparse.rows_selected",
                steps="serve.decode_steps",
                experts="serve.moe.experts_touched")
    run = types.SimpleNamespace(counters=counters)
    got = reader.read(cell=cell, run=run, trace=trace, **args)
    assert seen == {"keys": 80000.0, "rows": 40960.0, "batch": 4.0,
                    "experts": 2.5}
    # bytes bind: 5e8 / 1e11 = 5 ms of the median 20 ms
    assert got == pytest.approx(25.0)
    for missing in ("serve.sparse.keys_scored", "serve.sparse.rows_selected",
                    "serve.moe.experts_touched", "serve.decode_steps"):
        less = types.SimpleNamespace(counters={
            k: v for k, v in counters.items() if k != missing})
        assert reader.read(cell=cell, run=less, trace=trace, **args) is None
    assert reader.read(cell=cell, run=run, trace=None, **args) is None


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "glm_moe_dsa")
    config = harness.load_json("configs", "glm-5.2-d5.json")
    # W_qa 6144 x 2048, W_qb 2048 x 64 x 256, W_kva 6144 x 576,
    # W_kvb 512 x 64 x 448, W_o 64 x 256 x 6144
    assert family.attention_params(config) == \
        12_582_912 + 33_554_432 + 3_538_944 + 14_680_064 + 100_663_296 \
        == 165_019_648
    # W^I_q 2048 x 32 x 128, W^I_k 6144 x 128, W^I_w 6144 x 32
    assert family.indexer_params(config) == \
        8_388_608 + 786_432 + 196_608 == 9_371_648
    assert family.expert_params(config) == 3 * 6144 * 2048 == 37_748_736
    dense = 165_019_648 + 9_371_648 + 3 * 6144 * 12288
    assert dense == 400_883_712
    outside = 165_019_648 + 6144 * 256 + 37_748_736     # router, shared
    shared_layer = outside + 16 * 37_748_736
    assert shared_layer == 808_321_024
    head = 6144 * 19360
    assert family.fixed_params(config) == \
        dense + 4 * outside + 9_371_648 + head == 1_346_568_192
    assert family.held_params(config) == \
        dense + 3 * shared_layer + shared_layer + 9_371_648 + 2 * head \
        == 3_881_435_136
    assert round(2 * family.held_params(config) / 1e9, 2) == 7.76
    # of a token's 8 experts one in sixteen lies here, on average
    assert family.active_params(config) == \
        1_346_568_192 + 4 * 0.5 * 37_748_736
    # a token's rows: 5 latent rows of 576 and 2 index keys of 128, bf16
    assert family.row_bytes(config) == (5 * 576 + 2 * 128) * 2 == 6272
    w = family.widths(config)
    assert w["indexer"] == ("full", "shared", "shared", "shared", "full")
    assert w["experts"] == 256 and w["held"] == 16 and w["topk"] == 2048
    # a step of 4 slots at 10,000 rows each, 2 experts touched a layer
    flops, nbytes = family.decode_step_cost(
        config, keys_scored=2 * 4 * 10000, rows_selected=5 * 4 * 2048,
        batch=4, experts_touched=2.0)
    assert nbytes == (1_346_568_192 + 4 * 2 * 37_748_736) * 2 + \
        (80000 * 128 + 40960 * 576) * 2 + 4 * 6272
    assert nbytes / 819e9 > flops / 197e12          # HBM-bound


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row under the same key, but the
    five keys `reduced` names; the lists of layer kinds whole."""
    config = harness.load_json("configs", "glm-5.2-d5.json")
    want = {"hidden_size": 6144, "num_attention_heads": 64,
            "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
            "qk_head_dim": 256, "v_head_dim": 256, "head_dim": 192,
            "q_lora_rank": 2048, "kv_lora_rank": 512, "index_n_heads": 32,
            "index_head_dim": 128, "index_topk": 2048,
            "moe_intermediate_size": 2048, "intermediate_size": 12288,
            "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
            "n_shared_experts": 1, "max_position_embeddings": 1048576}
    assert {k: config[k] for k in want} == want
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "n_routed_experts", "vocab_size",
                                 "num_nextn_predict_layers"]
    assert config["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880,
        "num_nextn_predict_layers": 1}
    assert len(config["indexer_types"]) == len(config["mlp_layer_types"]) == 78
    held = config["held"]["layers"]
    assert [config["indexer_types"][i] for i in held] == \
        ["full", "shared", "shared", "shared", "full"]
    assert [config["mlp_layer_types"][i] for i in held] == \
        ["dense"] + ["sparse"] * 4
    traffic = harness.load_json("traffic", "longctx.json")
    assert traffic["prompt_tokens"] == [3072, 22528]
    assert traffic["output_tokens"] == [128, 512]
    assert traffic["max_total_tokens"] == 24576
    assert traffic["shape_seed"] == 20261003
