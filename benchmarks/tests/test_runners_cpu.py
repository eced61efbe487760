"""Rehearsal of the runners on the CPU at toy size: control flow, the
result's shape, and that each plain reference agrees with the system."""

import math

import pytest

from benchmarks.tests import toy


def test_gpt_training_cell_runs_and_matches_its_reference(tmp_path):
    from benchmarks.runners import train

    traffic = {"generator": "lm_batches", "seq_len": 32, "micro_batch": 2}
    run = train.run(toy.cell(toy.GPT, toy.train_workload("first_step"),
                             traffic, tmp=tmp_path))
    check = run.notes[1]
    assert check["same_initial_weights"] and check["losses_finite"]
    assert check["relative_difference"] <= 2e-3, check
    assert abs(check["reference_loss"] - math.log(256)) < 0.2
    # one real update: the loss fell, and by what the reference's step gives
    assert check["reference_loss_after_one_step"] < check["reference_loss"]
    assert abs(check["fell_over_reference_fell"] - 1.0) < 0.1, check
    assert run.correct and run.failed == 0 and run.attempted >= 1
    assert run.end_to_end["train_tokens_per_s"] > 0
    assert run.end_to_end["setup_s"] > 0
    assert {"bench.feed", "bench.dispatch", "bench.read_loss"} \
        <= set(run.host_spans)
    assert run.trace_path is None


def test_bert_training_cell_checks_the_dropout_off_loss(tmp_path):
    from benchmarks.runners import train

    traffic = {"generator": "mlm_batches", "seq_len": 32, "micro_batch": 4,
               "mask_share": 0.15}
    run = train.run(toy.cell(toy.BERT, toy.train_workload("eval_batch"),
                             traffic, tmp=tmp_path, trace=True))
    check = run.notes[1]
    assert check["relative_difference"] <= 2e-3, check
    # one real update, with dropout on: the step itself against the
    # reference's (as large, and nearly as far down its gradient)
    update = check["update_against_reference"]
    assert abs(update["size"] - 1.0) < 0.01, update
    assert 0.9 < update["down_gradient_least"] <= update["down_gradient"] \
        <= 1.0, update
    assert run.correct
    assert run.trace_path and run.trace_path.endswith(".xplane.pb")
    assert run.shapes["steps_traced"] == 2


def test_training_on_a_mesh_of_four(tmp_path):
    from benchmarks.runners import train

    traffic = {"generator": "lm_batches", "seq_len": 32, "micro_batch": 2}
    run = train.run(toy.cell(toy.GPT, toy.train_workload("first_step", 2),
                             traffic, tmp=tmp_path, chips=4))
    assert run.correct, run.notes
    assert run.notes[0]["tokens_per_step"] == 2 * 4 * 32
    assert run.notes[0]["compiles_in_window"] == 0


@pytest.mark.parametrize("trace", [False, True])
def test_serving_cell_runs_and_matches_its_reference(tmp_path, monkeypatch,
                                                     trace):
    from benchmarks.runners import serve

    # a toy step on the CPU takes ~0.6 ms, under the observer's 5 ms tick:
    # a request's tokens would be stamped at one look and the gaps' 95th
    # percentile could read 0.  Here the observer looks every 0.5 ms, so
    # the step is longer than the tick, as it is in every cell on the chip
    monkeypatch.setattr(serve.TokenObserver.__init__, "__defaults__",
                        (0.0005,))
    run = serve.run(toy.cell(toy.GPT, toy.SERVE, toy.CHAT, tmp=tmp_path,
                             seconds=1.5, trace=trace))
    load, check = run.notes
    assert run.attempted == 30 and run.failed == 0, load
    assert check["requests"] == 30 and run.correct, check  # all of them
    assert load["output_tokens"] == check["positions"]
    assert load["observer_late_ms_max"] >= 0
    assert check["worst_gap_to_top_logit"] <= 0.2
    for name in ("serve_ttft_p95_ms", "serve_itl_p95_ms",
                 "serve_tokens_per_s", "setup_s"):
        assert run.end_to_end[name] > 0, name
    assert 0 < load["itl_ms_median"] <= run.end_to_end["serve_itl_p95_ms"] \
        <= load["itl_ms_max"]
    assert run.counters["serve.decode_steps"]["calls"] > 0
    if trace:
        waits = [e for e in run.program_spans if e["name"] == "queue_wait"]
        assert len(waits) == 30
        assert run.trace_path


@pytest.mark.parametrize("rtol", [2e-3, 0.0])
def test_the_command_prints_the_contract_line(tmp_path, monkeypatch, capsys,
                                              rtol):
    """run.py's own path, steered onto the CPU from the test (the program
    has no CPU mode): toy sizes under the first cell's name.  With a
    tolerance nothing meets, the line says `correct: false` and the
    check's numbers go to stderr."""
    import json
    import logging
    import sys

    import jax

    from benchmarks import harness, run

    with open(run.ROOT + "/BENCHMARK.json") as f:
        bm = json.load(f)
    entry = bm["workloads"][0]
    workload = toy.train_workload("first_step")
    workload["check"]["rtol"] = rtol
    toys = {"workloads": workload, "configs": toy.GPT,
            "traffic": {"generator": "lm_batches", "seq_len": 32,
                        "micro_batch": 2}}
    monkeypatch.setattr(run, "check_devices",
                        lambda chips: (jax.devices()[:chips], toy.PEAKS))
    monkeypatch.setattr(run, "load_json", lambda kind, name:
                        toys.get(kind) or harness.load_json(kind, name))
    monkeypatch.setattr(run, "SCRATCH", str(tmp_path))
    try:
        rc = run.main(["--workload", entry["name"], "--seed",
                       str(2 ** 31 + 5), "--seconds", "0.5", "--trace", "0"])
    finally:  # run.py pointed the program's log at the captured stderr
        for handler in logging.getLogger("deepspeed_tpu").handlers:
            handler.stream = sys.__stderr__
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    assert ("run.py: incorrect: " in captured.err) == (rtol == 0.0)
    assert rc == 0 and all(ln.startswith("# ") for ln in lines[:-1])
    last = json.loads(lines[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert last["check"]["rtol"] == rtol and "compiles_in_window" in \
        last["check"]
    assert json.loads(captured.err.strip().splitlines()[-1].split(
        ": ", 2)[2]) == last["check"]
    want = {m["name"] for m in bm["end_to_end"]
            if entry["name"] in m.get("workloads", [entry["name"]])}
    assert set(last["metrics"]) == want and "setup_s" in want
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["correct"] is (rtol > 0) and last["failed"] == 0
