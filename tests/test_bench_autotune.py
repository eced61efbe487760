"""CPU dry-run of bench.py's autotune/cache/fallback state machine.

`_time_config` and the device check are stubbed (a rankable table, a
described chip), so every branch of the machine — probe, A/B, cache
write, cache hit, stale fingerprint, truncated probe, winner-fails
fallback — runs in milliseconds with deterministic outcomes; the last
test takes the stubs away and sees `main()` refuse the CPU."""

import importlib.util
import json
import os
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def bench(tmp_path, monkeypatch):
    """Fresh bench module whose artifact dir is an isolated tmp_path (the
    real bench_artifacts/ must never be touched by tests)."""
    for k in list(os.environ):
        if k.startswith("DSTPU_"):
            monkeypatch.delenv(k)
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", ROOT / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.__file__ = str(tmp_path / "bench.py")
    monkeypatch.setattr(mod, "_dense_peak_tflops", lambda *a, **k: 0.0)
    monkeypatch.setattr(mod, "_device", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1})
    return mod


def _stub_time_config(bench, monkeypatch, table, calls):
    """table: (size, micro, remat, attn_impl) -> tflops | Exception."""

    def fake(size, seq, micro, remat, steps, warmup=2, attn_impl="auto"):
        calls.append({"size": size, "micro": micro, "remat": remat,
                      "steps": steps, "attn_impl": attn_impl})
        v = table.get((size, micro, remat, attn_impl),
                      table.get((size, micro, remat, "auto"), 1.0))
        if isinstance(v, Exception):
            raise v
        return {"size": size, "seq": seq, "micro": micro, "remat": remat,
                "attn_impl": attn_impl, "n_params": 1_000_000, "n_dev": 1,
                "tok_s_chip": 100.0, "tflops": float(v)}

    monkeypatch.setattr(bench, "_time_config", fake)


def _cache_path(bench):
    return os.path.join(os.path.dirname(os.path.abspath(bench.__file__)),
                        "bench_artifacts", "autotune.json")


# ranks ("medium", 16, True) highest; its xla A/B probe even higher
RANKED = {("small", 8, False, "auto"): 1.0,
          ("small", 32, False, "auto"): 2.0,
          ("medium", 8, False, "auto"): 3.0,
          ("medium", 16, True, "auto"): 4.0,
          ("medium", 16, True, "xla"): 5.0}


def test_probe_picks_winner_runs_ab_and_caches(bench, monkeypatch):
    calls = []
    _stub_time_config(bench, monkeypatch, RANKED, calls)
    out = bench.run_bench()
    # winner config measured with the A/B-selected kernel choice
    assert out["metric"].startswith("gpt2_medium")
    assert out["micro_batch"] == 16 and out.get("remat") is True
    assert out["attn_impl"] == "xla"
    # 4 probes + 1 xla A/B + 1 final measurement
    assert len(calls) == 6
    assert calls[-1]["steps"] > 3  # the full measurement, not a probe
    cached = json.load(open(_cache_path(bench)))
    assert (cached["size"], cached["micro"], cached["remat"],
            cached["attn_impl"]) == ("medium", 16, True, "xla")
    assert cached["fingerprint"]["seq"] == out["seq_len"]


def test_cache_hit_skips_probing(bench, monkeypatch):
    calls = []
    _stub_time_config(bench, monkeypatch, RANKED, calls)
    first = bench.run_bench()
    calls.clear()
    out = bench.run_bench()
    # only the final measurement ran; provenance is flagged
    assert len(calls) == 1 and calls[0]["steps"] > 3
    assert out["autotune_cached"] is True
    assert "autotune_probes" not in out
    assert out["metric"] == first["metric"]


def test_stale_fingerprint_reprobes(bench, monkeypatch):
    calls = []
    _stub_time_config(bench, monkeypatch, RANKED, calls)
    bench.run_bench()
    # poison the fingerprint (e.g. probed on another backend/seq)
    path = _cache_path(bench)
    cached = json.load(open(path))
    cached["fingerprint"]["seq"] = 31337
    json.dump(cached, open(path, "w"))
    calls.clear()
    out = bench.run_bench()
    assert len(calls) == 6  # full re-probe, not a cache pin
    assert "autotune_cached" not in out
    assert json.load(open(path))["fingerprint"]["seq"] == out["seq_len"]


def test_truncated_probe_not_cached(bench, monkeypatch):
    calls = []
    table = dict(RANKED)
    table[("medium", 8, False, "auto")] = RuntimeError(
        "RESOURCE_EXHAUSTED: out of memory")
    _stub_time_config(bench, monkeypatch, table, calls)
    out = bench.run_bench()
    # the failed probe is recorded, the headline still lands on the
    # best SURVIVING candidate, and the degraded probe set is NOT cached
    assert any(p.get("failed") and p.get("oom")
               for p in out["autotune_probes"])
    assert out["micro_batch"] == 16
    assert not os.path.exists(_cache_path(bench))


def test_winner_fails_falls_back_to_default(bench, monkeypatch):
    calls = []
    _stub_time_config(bench, monkeypatch, RANKED, calls)
    bench.run_bench()  # populate the cache with the winner
    table = dict(RANKED)
    # the cached winner no longer runs (chip change / OOM)
    table[("medium", 16, True, "xla")] = RuntimeError(
        "RESOURCE_EXHAUSTED: out of memory")
    table[("medium", 16, True, "auto")] = RuntimeError(
        "RESOURCE_EXHAUSTED: out of memory")
    calls.clear()
    _stub_time_config(bench, monkeypatch, table, calls)
    out = bench.run_bench()
    assert out["metric"].startswith("gpt2_small")
    assert out["micro_batch"] == 8
    assert "autotune_cached" not in out  # provenance flag cleared
    assert calls[-1] == {"size": "small", "micro": 8, "remat": False,
                         "steps": calls[-1]["steps"], "attn_impl": "auto"}


def test_result_names_the_device(bench, monkeypatch):
    calls = []
    _stub_time_config(bench, monkeypatch, RANKED, calls)
    out = bench.run_bench()
    assert (out["platform"], out["device_kind"], out["device_count"]) == \
        ("tpu", "TPU v5 lite", 1)


def test_main_exits_nonzero_without_a_chip(bench, monkeypatch, capsys):
    """No TPU, no line: the real `_device` check raises before
    anything is compiled or printed (the tests run on the CPU)."""
    import jax

    monkeypatch.undo()  # the real _device, not the fixture's stand-in
    before = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(RuntimeError, match="measures on a TPU only"):
            bench.main()
    finally:  # main() turned the compile cache on; the tests run without
        jax.config.update("jax_compilation_cache_dir", before)
    assert capsys.readouterr().out.strip() == ""
