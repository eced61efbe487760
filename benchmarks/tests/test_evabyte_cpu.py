"""Rehearsal of the EvaByte serving cell on the CPU at toy size: the real
runner, generator, family module, reference and the new per-layer
metrics' files.  Nothing here is a device number."""

import pytest

from benchmarks import harness
from benchmarks.tests import toy

EVABYTE = {"family": "evabyte", "hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 4, "intermediate_size": 176,
           "num_hidden_layers": 2, "vocab_size": 320, "num_pred_heads": 8,
           "window_size": 32, "chunk_size": 4, "rms_norm_eps": 1e-5,
           "rope_theta": 100000, "max_position_embeddings": 256,
           "reduced": [],
           "assumed": {"init": {"std": 0.01275, "attn_out_std": 0.3,
                                "pool_std": 1.0}}}
SERVE = {"runner": "serve",
         "serve": {"block_size": 4, "num_blocks": 97, "max_batch": 3,
                   "prefill_chunk": 8, "max_seq_len": 128,
                   "prefix_cache": False},
         "model": {"param_dtype": "bfloat16"}, "drain_seconds": 30,
         "check": {"requests": 6, "batch": 1, "logit_margin": 0.02},
         "trace": {"seconds": 0.3}}
LONGDOC = {"generator": "poisson_lengths", "rate_rps": 12.0,
           "prompt_tokens": [20, 100], "output_tokens": [4, 20],
           "max_total_tokens": 128, "shape_seed": 7}


def _read(name, cell, run):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.plugin("readers", spec["reader"]).read(
        cell=cell, run=run, trace=None, **spec["args"])


@pytest.mark.parametrize("trace", [False, True])
def test_evabyte_serving_cell_runs_and_matches_its_reference(tmp_path, trace):
    from benchmarks.runners import serve

    cell = toy.cell(EVABYTE, SERVE, LONGDOC, tmp=tmp_path, trace=trace,
                    seconds=1.0)
    run = serve.run(cell)
    load, check = run.notes
    assert run.correct and run.failed == 0, run.notes
    assert load["compiles_in_window"] == 0
    assert check["requests"] == 6
    assert check["worst_gap_to_top_logit"] <= 0.02, check
    rows = _read("eva_rows_per_query.serve", cell, run)
    share = _read("eva_context_share_pct.serve", cell, run)
    assert 1 <= rows <= 32 + 128 // 4
    assert 0 < share <= 100
    assert _read("kv_window_closes.serve", cell, run) >= 1
    # the roofline share needs a device trace: nothing to read here
    assert _read("decode_hbm_roofline.serve", cell, run) is None


def test_costs_of_the_published_configuration():
    family = harness.plugin("models", "evabyte")
    config = harness.load_json("configs", "evabyte-d16.json")
    assert family.matmul_params(config) == \
        16 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 8 * 320
    assert family.row_bytes(config) == 2 * 16 * 32 * 128 * 2  # 256 KiB
    flops, nbytes = family.decode_step_cost(config, rows_read=8 * 1400,
                                            batch=8)
    # the weights once (6.5 GB) and 11,208 rows of 256 KiB
    assert abs(nbytes - (2 * family.matmul_params(config)
                         + 11208 * 262144)) < 1
    assert flops / 197e12 < nbytes / 819e9     # HBM-bound
    model = family.build(config, seq_len=16384, n_dev=1,
                         param_dtype="bfloat16")
    c = model.config
    assert (c.num_layers, c.d_model, c.num_heads, c.head_dim, c.d_ff,
            c.window_size, c.chunk_size, c.vocab_size, c.num_pred_heads) \
        == (16, 4096, 32, 128, 11008, 2048, 16, 320, 8)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        family.build(config, seq_len=65536, n_dev=1)


def test_the_configuration_file_keeps_every_published_size():
    import json

    config = harness.load_json("configs", "evabyte-d16.json")
    row = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(json.loads(line) for line in f
                       if '"name": "EvaByte"' in line)
    except OSError:
        pytest.skip("the catalog is not on this machine")
    differ = [k for k, v in row["config"].items() if config.get(k) != v]
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == row["source_url"]
