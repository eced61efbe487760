"""What `BENCHMARK.json` names exists, names and units use only the allowed
characters, and run.py refuses to run without the chip."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import harness

ROOT = os.path.dirname(harness.BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_lengths(bm):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bm[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for w in bm["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(bm["workloads"]) // 4)
    assert 1 <= bm["run_seconds"] <= 51
    assert len(json.dumps(bm)) < 64 * 1024


def test_every_file_a_cell_names_exists(bm):
    configs = {c["name"]: c for c in bm["configs"]}
    for w in bm["workloads"]:
        cell = harness.load_json("workloads", w["name"] + ".json")
        assert os.path.exists(os.path.join(
            harness.BENCH, "runners", cell["runner"] + ".py"))
        c = configs[w["config"]]
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        cfg = harness.load_json("configs", c["name"] + ".json")
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for kind in ("models", "reference"):
            assert os.path.exists(os.path.join(
                harness.BENCH, kind, cfg["family"] + ".py"))
        mix = harness.load_json("traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            harness.BENCH, "traffic", mix["generator"] + ".py"))
    assert {w["config"] for w in bm["workloads"]} == set(configs)


def test_every_metric_has_its_reader_and_moves_what_its_cells_report(bm):
    cells = [w["name"] for w in bm["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bm["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in next(
        m for m in bm["end_to_end"] if m["name"] == "setup_s")
    layers = set()
    for m in bm["per_layer"]:
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        for key in ("layer", "unit", "better", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            harness.BENCH, "readers", spec["reader"] + ".py"))
        assert hasattr(harness.plugin("readers", spec["reader"]), "read")
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]], (m["name"], cell)
        layers.add(m["layer"])
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    for cell in cells:  # setup_s, another end-to-end metric, a per-layer one
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells) for m in bm["per_layer"])


def test_files_under_paths_are_named_from_allowed_characters(bm):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bm["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel


def test_every_layer_metric_file_is_well_formed():
    d = os.path.join(harness.BENCH, "layer_metrics")
    for f in sorted(os.listdir(d)):
        assert NAME.match(f[:-5]), f
        spec = harness.load_json("layer_metrics", f)
        assert UNIT.match(spec["unit"]) and spec["source"] in SOURCES
        assert os.path.exists(os.path.join(
            harness.BENCH, "readers", spec["reader"] + ".py"))


def test_run_refuses_without_the_chip_and_prints_no_result(bm):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, *bm["command"][1].split("/")),
         "--workload", bm["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_readers_leave_out_what_they_cannot_read(tmp_path):
    from benchmarks.tests import toy

    run = harness.RunResult(end_to_end={}, correct=True, attempted=1,
                            failed=0, notes=[], memory_peak_bytes=0)
    d = os.path.join(harness.BENCH, "layer_metrics")
    cell = toy.cell(toy.GPT, {}, {"generator": "lm_batches"}, tmp=tmp_path)
    for f in sorted(os.listdir(d)):
        spec = harness.load_json("layer_metrics", f)
        got = harness.plugin("readers", spec["reader"]).read(
            cell=cell, run=run, trace=None, **spec["args"])
        assert got is None, f
