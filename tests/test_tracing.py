"""Distributed trace timelines (monitor/tracing.py + trace_report).

THE acceptance pins: tracing disabled is a true zero (no trace files,
no recorder thread, bitwise-identical loss and token streams); enabled,
the training step and the serving request lifecycle land as structured
span events that tools/trace_report.py merges into Chrome/Perfetto
JSON with clock-skew alignment; the ServingSLO window reproduces
serve_bench's nearest-rank percentiles; the watchdog trip snapshot
ships the flight-recorder trace tail.  Plus the counter/doc lint: every
literal counter the code bumps is documented in docs/tutorials/, and
the µs-in-bytes convention set matches the docs.
"""

import glob
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

import deepspeed_tpu as ds
from deepspeed_tpu.monitor import COUNTERS, DeepSpeedMonitorConfig
from deepspeed_tpu.monitor.counters import US_IN_BYTES_COUNTERS
from deepspeed_tpu.monitor.tracing import (TRACE_CATEGORIES,
                                           TRACE_FILE_PREFIX,
                                           ServingSLO, TraceRecorder,
                                           _sample_hash,
                                           percentile_nearest_rank,
                                           read_trace_file)
from tests.simple_model import SimpleModel, random_batches

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

REPO = os.path.join(os.path.dirname(__file__), "..")
FLUSH_THREAD = "dstpu-trace-flush"


def engine_cfg(tmp_path, job="run", tracing=None):
    mon = {"enabled": True, "output_path": str(tmp_path),
           "job_name": job, "flush_interval": 1}
    if tracing is not None:
        mon["tracing"] = tracing
    return {
        "train_batch_size": 32,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 0,
        "monitor": mon,
    }


def train_losses(tmp_path, job, tracing=None, steps=4):
    engine, *_ = ds.initialize(model=SimpleModel(),
                               config=engine_cfg(tmp_path, job, tracing))
    losses = []
    for b in random_batches(steps):
        losses.append(float(engine.forward(b)))
        engine.backward()
        engine.step()
    engine.finalize_monitoring()
    return losses


def trace_files(tmp_path, job):
    return sorted(glob.glob(
        str(tmp_path / job / f"{TRACE_FILE_PREFIX}*.jsonl")))


def flush_threads():
    return [t for t in threading.enumerate() if t.name == FLUSH_THREAD]


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_tracing_config_defaults_off():
    cfg = DeepSpeedMonitorConfig({"monitor": {"enabled": True}})
    assert cfg.tracing_enabled is False
    assert cfg.tracing_sample_rate == 1.0


def test_tracing_config_strict_validation():
    def mon(tr):
        return {"monitor": {"enabled": True, "tracing": tr}}

    with pytest.raises(ValueError, match="unknown key"):
        DeepSpeedMonitorConfig(mon({"enabled": True, "samplerate": 0.5}))
    with pytest.raises(ValueError, match="sample_rate"):
        DeepSpeedMonitorConfig(mon({"enabled": True, "sample_rate": 1.5}))
    with pytest.raises(ValueError, match="buffer_events"):
        DeepSpeedMonitorConfig(mon({"enabled": True, "buffer_events": 1}))
    with pytest.raises(ValueError, match="must be a bool"):
        DeepSpeedMonitorConfig(mon({"enabled": "yes"}))
    with pytest.raises(ValueError, match="unknown key"):
        DeepSpeedMonitorConfig(mon({"enabled": True,
                                    "slo": {"windows": 1}}))
    # tracing requires the monitor: the files land in its run dir
    with pytest.raises(ValueError, match="monitor.enabled"):
        DeepSpeedMonitorConfig({"monitor": {"enabled": False,
                                            "tracing": {"enabled": True}}})


# ---------------------------------------------------------------------------
# recorder unit
# ---------------------------------------------------------------------------

def test_recorder_roundtrip_and_footer(tmp_path):
    rec = TraceRecorder(str(tmp_path), rank=3, flush_interval_s=10)
    with rec.span("apply", "train", step=7):
        pass
    rec.instant("watchdog_beat", "watchdog", step=7)
    rec.add_complete("queue_wait", "serve", dur_us=1500, rid=0)
    rec.close()
    assert not flush_threads(), "close() must join the writer thread"

    segments, summary = read_trace_file(
        str(tmp_path / f"{TRACE_FILE_PREFIX}00003.jsonl"))
    assert len(segments) == 1
    meta, events = segments[0]
    assert meta["rank"] == 3 and "sync_mono_us" in meta
    assert [e["name"] for e in events] == ["apply", "watchdog_beat",
                                           "queue_wait"]
    assert events[0]["ph"] == "X" and events[1]["ph"] == "i"
    # the back-dated external span ends at its recording instant
    assert events[2]["dur"] == 1500
    assert summary["rank"] == 3 and summary["events"] == 3
    assert summary["dropped"] == 0
    # close is idempotent: no double footer
    rec.close()
    _, summary2 = read_trace_file(
        str(tmp_path / f"{TRACE_FILE_PREFIX}00003.jsonl"))
    assert summary2["events"] == 3


def test_recorder_byte_cap_drops_and_counts(tmp_path):
    rec = TraceRecorder(str(tmp_path), max_file_bytes=4096,
                        flush_interval_s=10)
    for i in range(500):
        rec.instant("beat", "watchdog", i=i, pad="x" * 64)
    rec.close()
    segments, summary = read_trace_file(
        str(tmp_path / f"{TRACE_FILE_PREFIX}00000.jsonl"))
    _, events = segments[0]
    assert summary["dropped"] > 0
    # footer `events` counts everything recorded; written = events-dropped
    assert summary["events"] == 500
    assert len(events) == 500 - summary["dropped"]
    assert os.path.getsize(
        str(tmp_path / f"{TRACE_FILE_PREFIX}00000.jsonl")) < 4096 + 1024


def test_recorder_multi_segment_append(tmp_path):
    for run in range(2):
        rec = TraceRecorder(str(tmp_path), flush_interval_s=10)
        rec.instant("start", "train", run=run)
        rec.close()
    segments, summary = read_trace_file(
        str(tmp_path / f"{TRACE_FILE_PREFIX}00000.jsonl"))
    assert len(segments) == 2
    assert [seg[1][0]["args"]["run"] for seg in segments] == [0, 1]
    # the footer is the LAST segment's; each segment got its own meta
    assert summary["events"] == 1


def test_flight_recorder_ring_is_bounded(tmp_path):
    rec = TraceRecorder(str(tmp_path), buffer_events=16,
                        flush_interval_s=10)
    for i in range(100):
        rec.instant("beat", "watchdog", i=i)
    tail = rec.last_events()
    assert len(tail) == 16
    assert tail[-1]["args"]["i"] == 99
    assert rec.last_events(4)[0]["args"]["i"] == 96
    rec.close()


def test_sampling_is_deterministic(tmp_path):
    """Same seed + same key schedule => the identical trace, run to
    run — diffable timelines (and rank-agreement for step keys)."""
    def record(sub):
        d = tmp_path / sub
        d.mkdir()
        rec = TraceRecorder(str(d), sample_rate=0.4, seed=11,
                            flush_interval_s=10)
        for step in range(1, 41):
            if rec.sampled(step):
                rec.add_complete("dispatch.full", "train", ts_us=step,
                                 dur_us=1, step=step)
        for rid in range(40):
            if rec.sampled(f"rid:{rid}"):
                rec.instant("finish", "serve", rid=rid)
        rec.close()
        segments, _ = read_trace_file(
            str(d / f"{TRACE_FILE_PREFIX}00000.jsonl"))
        return [(e["name"], e["args"]) for e in segments[0][1]]

    a, b = record("a"), record("b")
    assert a == b
    names = [n for n, _ in a]
    # the 0.4 gate actually thinned both populations (not all, not none)
    assert 0 < names.count("dispatch.full") < 40
    assert 0 < names.count("finish") < 40
    # a (very) different seed picks a different subset — crc32 is
    # linear, so NEARBY seeds barely perturb the hash; the gate only
    # promises determinism per seed, not independence across seeds
    other = [s for s in range(1, 41) if _sample_hash(999983, s) < 0.4]
    mine = [int(args["step"]) for n, args in a if n == "dispatch.full"]
    assert other != mine


# ---------------------------------------------------------------------------
# THE acceptance pins: disabled is a true zero
# ---------------------------------------------------------------------------

def test_disabled_tracing_zero_files_threads_and_bitwise_loss(tmp_path):
    assert not flush_threads()
    base = train_losses(tmp_path, "base", tracing=None)
    assert not flush_threads()
    assert trace_files(tmp_path, "base") == []

    traced = train_losses(tmp_path, "traced", tracing={"enabled": True})
    assert not flush_threads(), "finalize_monitoring must join the writer"
    assert len(trace_files(tmp_path, "traced")) == 1

    # observation changes NOTHING: bitwise-identical losses
    assert traced == base


def test_training_timeline_content(tmp_path):
    train_losses(tmp_path, "t", tracing={"enabled": True,
                                         "flush_interval_s": 0.1})
    [path] = trace_files(tmp_path, "t")
    segments, summary = read_trace_file(path)
    events = segments[0][1]
    names = {e["name"] for e in events}
    assert "dispatch.full" in names  # fused single-dispatch step path
    steps = sorted({e["args"]["step"] for e in events
                    if e["name"] == "dispatch.full"})
    assert steps == [1, 2, 3, 4]
    for e in events:
        assert e["cat"] in TRACE_CATEGORIES
    assert summary["dropped"] == 0
    # recorder self-accounting: real values, not the µs convention
    tot = COUNTERS.totals().get("trace.events")
    assert tot and tot["calls"] > 0 and tot["bytes"] > 0


def test_training_phases_and_the_lowered_step(tmp_path):
    """The host's three stretches before a fused step is in flight land
    in the recorder in order, `train.launch` under its old name; the
    step lowers to the same text with tracing on and off."""

    def lowered(job, tracing):
        engine, *_ = ds.initialize(model=SimpleModel(),
                                   config=engine_cfg(tmp_path, job, tracing))
        counted, texts = engine._step_fns["full"], []

        class Capture:
            fn = counted.fn

            def __call__(self, *args):
                texts.append(counted.fn.lower(*args).as_text())
                return counted(*args)

        engine._step_fns["full"] = Capture()
        for b in random_batches(2):
            engine.forward(b)
            engine.backward()
            engine.step()
        engine.finalize_monitoring()
        return texts

    traced = lowered("on", {"enabled": True, "flush_interval_s": 0.1})
    assert traced == lowered("off", None) and len(traced) == 2
    [path] = trace_files(tmp_path, "on")
    events = read_trace_file(path)[0][0][1]
    for step in (1, 2):
        mine = sorted((e for e in events if e["ph"] == "X"
                       and e.get("args", {}).get("step") == step
                       and e["cat"] == "train"), key=lambda e: e["ts"])
        assert [e["name"] for e in mine] == [
            "train.settle_flag", "train.inputs", "dispatch.full"]
        for a, b in zip(mine, mine[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
    assert not any(e["name"] == "train.launch" for e in events)


def test_training_sampling_thins_whole_steps(tmp_path):
    train_losses(tmp_path, "s",
                 tracing={"enabled": True, "sample_rate": 0.5,
                          "seed": 3}, steps=8)
    [path] = trace_files(tmp_path, "s")
    segments, _ = read_trace_file(path)
    steps = sorted({e["args"]["step"] for e in segments[0][1]
                    if e["name"] == "dispatch.full"})
    # per-step gating matches the recorder's deterministic hash: whole
    # steps in or out, never a partial step's events
    expect = [s for s in range(1, 9) if _sample_hash(3, s) < 0.5]
    assert steps == expect
    assert 0 < len(steps) < 8


# ---------------------------------------------------------------------------
# ServingSLO
# ---------------------------------------------------------------------------

def test_slo_percentiles_match_serve_bench():
    import serve_bench
    rs = np.random.RandomState(0)
    xs = rs.gamma(2.0, 10.0, size=37).tolist()
    for q in (50, 90, 99):
        assert percentile_nearest_rank(sorted(xs), q) == \
            pytest.approx(serve_bench._percentile(xs, q))


def test_slo_window_snapshot_and_emit():
    clock = [0.0]
    out = []
    slo = ServingSLO(emit=out.append, window_s=10.0, emit_interval_s=2.0,
                     clock=lambda: clock[0])
    for ms in (10.0, 20.0, 30.0, 40.0):
        slo.observe_ttft(ms / 1e3)
    slo.observe_tokens(30)
    slo.observe_queue_depth(2)
    slo.observe_queue_depth(4)
    slo.observe_accept(3, 8)
    slo.observe_shed(1)
    clock[0] = 5.0
    snap = slo.force()
    assert snap["requests"] == 4
    assert snap["ttft_ms"]["p50"] == pytest.approx(20.0)
    assert snap["ttft_ms"]["p99"] == pytest.approx(40.0)
    assert snap["tok_per_s"] == pytest.approx(30 / 5.0)
    assert snap["queue_depth_mean"] == pytest.approx(3.0)
    assert snap["accept_rate"] == pytest.approx(3 / 8)
    assert snap["shed"] == 1
    assert out and out[-1] == snap
    # the window actually slides: old observations expire
    clock[0] = 20.0
    snap2 = slo.force()
    assert snap2["requests"] == 0 and snap2["ttft_ms"]["n"] == 0
    # tick() is edge-triggered on the emit interval
    slo2 = ServingSLO(emit=None, window_s=10.0, emit_interval_s=2.0,
                      clock=lambda: clock[0])
    assert slo2.tick() is None        # first call primes, never emits
    clock[0] = 21.0
    assert slo2.tick() is None
    clock[0] = 23.0
    assert slo2.tick() is not None
    with pytest.raises(ValueError):
        ServingSLO(window_s=0.0)


# ---------------------------------------------------------------------------
# serving lifecycle + flight recorder
# ---------------------------------------------------------------------------

def _serve_fixture():
    from tests.test_serving import _cfg  # reuse the nano fixture shape
    from deepspeed_tpu.models import GPT, gpt2_config
    model = GPT(gpt2_config("nano", num_layers=2, num_heads=4,
                            d_model=32, vocab_size=64, max_seq_len=64))
    params = model.init(jax.random.PRNGKey(1))
    return model, params, _cfg


def test_serving_traced_lifecycle_token_identical(tmp_path):
    from deepspeed_tpu.serving import ServeEngine
    model, params, _cfg = _serve_fixture()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 64, (n,)).tolist() for n in (5, 9, 3)]

    plain = ServeEngine(model, params, _cfg())
    want = plain.generate(prompts, 6)

    eng = ServeEngine(model, params, _cfg(), programs=plain.programs)
    rec = TraceRecorder(str(tmp_path), flush_interval_s=10)
    slo_events = []
    slo = ServingSLO(emit=slo_events.append, window_s=60.0,
                     emit_interval_s=1e-6, tracer=rec)
    eng.attach_tracing(tracer=rec, slo=slo)
    got = eng.generate(prompts, 6)
    slo.force()
    rec.close()

    assert got == want, "tracing must not perturb token streams"
    segments, summary = read_trace_file(
        str(tmp_path / f"{TRACE_FILE_PREFIX}00000.jsonl"))
    events = segments[0][1]
    names = [e["name"] for e in events]
    for needed in ("queue_wait", "prefill_chunk", "first_token",
                   "decode_step", "finish", "slo_window"):
        assert needed in names, f"missing {needed} in {sorted(set(names))}"
    assert names.count("queue_wait") == len(prompts)
    assert names.count("finish") == len(prompts)
    rids = {e["args"]["rid"] for e in events if e["name"] == "first_token"}
    assert rids == {0, 1, 2}
    for e in events:
        if e["name"] == "decode_step":
            assert e["cat"] == "serve" and 1 <= e["args"]["batch"] <= 4
    assert summary["dropped"] == 0
    snap = slo_events[-1]
    assert snap["requests"] == len(prompts)
    assert snap["ttft_ms"]["n"] == len(prompts)


@pytest.mark.parametrize("family", ["gpt", "evabyte"])
def test_worker_driven_run_is_token_identical_with_a_recorder(tmp_path,
                                                              family):
    """A worker-driven run — requests join while others decode and leave
    at different steps; for EvaByte a window closes in mid-decode — gives
    the same tokens with a recorder at sampling 1 as with none, and the
    recorder saw every phase of the loop."""
    import time

    from deepspeed_tpu.serving import ServeEngine, ServeWorker

    if family == "gpt":
        model, params, cfg = _serve_fixture()
        rs = np.random.RandomState(5)
        jobs = [(rs.randint(0, 64, (n,)).tolist(), new)
                for n, new in ((5, 20), (9, 3), (3, 7), (12, 2))]
    else:
        from tests import test_evabyte as eva

        model = eva.EvaByte(eva._config())
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        cfg = eva._serve
        # 29 + 20 crosses the window of 32 three decode steps in
        jobs = [(eva._prompt(29, 0), 20), (eva._prompt(12, 1), 6),
                (eva._prompt(7, 2), 3)]

    def drive(recorder):
        eng = ServeEngine(model, params, cfg())
        if recorder is not None:
            eng.attach_tracing(tracer=recorder)
        reqs = [eng.submit(*jobs[0])]
        worker = ServeWorker(eng)
        worker.start()
        try:
            for job in jobs[1:]:
                while len(reqs[0].out) < 2 * len(reqs):
                    time.sleep(0.001)
                reqs.append(eng.submit(*job))
            deadline = time.time() + 120
            while eng.has_work() and time.time() < deadline:
                time.sleep(0.001)
        finally:
            worker.stop()
        assert all(r.state == "finished" for r in reqs)
        return [r.out for r in reqs]

    rec = TraceRecorder(str(tmp_path), buffer_events=1 << 16,
                        flush_interval_s=10)
    traced = drive(rec)
    names = {e["name"] for e in rec.last_events()}
    rec.close()
    assert traced == drive(None), "a recorder must not perturb the tokens"
    assert {"serve.admit", "serve.prefill.launch", "serve.decode.launch",
            "serve.decode.upload", "serve.read", "serve.bookkeep"} <= names
    assert ("eva.window_close" in names) == (family == "evabyte")


def test_watchdog_snapshot_ships_trace_tail(tmp_path):
    from deepspeed_tpu.runtime import resilience as rz
    rec = TraceRecorder(str(tmp_path), buffer_events=32,
                        flush_interval_s=10)
    for i in range(5):
        rec.instant("decode_step", "serve", step=i)
    run_dir = str(tmp_path / "wd")
    wd = rz.StepWatchdog(600.0, run_dir, rank=0)
    try:
        wd.set_flight_recorder(rec.last_events)
        wd.trip(1.0, step=5)
        with open(os.path.join(
                run_dir, "watchdog_snapshot.rank00000.1.json")) as f:
            snap = json.load(f)
        assert [e["args"]["step"] for e in snap["trace_tail"]] == \
            list(range(5))
        # a raising provider is swallowed, never propagated
        wd.beat(6)  # re-arm so the next trip records
        wd.set_flight_recorder(lambda: 1 / 0)
        wd.trip(1.0, step=6)
        with open(os.path.join(
                run_dir, "watchdog_snapshot.rank00000.2.json")) as f:
            snap2 = json.load(f)
        assert snap2["trace_tail"] == [
            {"error": "ZeroDivisionError: division by zero"}]
    finally:
        wd.stop()
        rec.close()


# ---------------------------------------------------------------------------
# TraceWindow failure paths (monitor/spans.py)
# ---------------------------------------------------------------------------

def test_trace_window_start_failure_disables_loudly(tmp_path, monkeypatch):
    from deepspeed_tpu.monitor.spans import TraceWindow

    def boom(*a, **k):
        raise RuntimeError("profiler backend unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    tw = TraceWindow(2, 3, str(tmp_path / "prof"))
    tw.tick(1)                       # before the window: no-op
    assert not tw.active and not tw.done
    tw.tick(2)                       # start raises -> disabled, not fatal
    assert tw.done and not tw.active
    tw.tick(3)                       # permanently inert afterwards
    assert tw.done and not tw.active
    tw.close()


def test_trace_window_stop_failure_still_completes(tmp_path, monkeypatch):
    from deepspeed_tpu.monitor.spans import TraceWindow

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: started.append(d))

    def boom():
        raise RuntimeError("stop exploded")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    tw = TraceWindow(0, 2, str(tmp_path / "prof"))
    tw.tick(0)
    assert tw.active and started == [str(tmp_path / "prof")]
    tw.tick(2)                       # stop raises -> window closes anyway
    assert tw.done and not tw.active
    tw.close()                       # idempotent after the failure

    # close() while active takes the same guarded stop path
    tw2 = TraceWindow(0, 10, str(tmp_path / "prof2"))
    tw2.tick(0)
    assert tw2.active
    tw2.close()
    assert tw2.done and not tw2.active


def test_trace_window_negative_start_is_disabled():
    from deepspeed_tpu.monitor.spans import TraceWindow
    tw = TraceWindow(-1, 1, "unused")
    assert tw.done
    tw.tick(0)
    tw.close()


# ---------------------------------------------------------------------------
# trace_report merge + selftest lane
# ---------------------------------------------------------------------------

def test_trace_report_selftest_cli():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert "selftest ok" in r.stdout


def test_trace_report_merges_engine_run(tmp_path):
    import trace_report
    train_losses(tmp_path, "m", tracing={"enabled": True})
    merged = trace_report.merge_runs([str(tmp_path / "m")])
    evs = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    assert evs and min(e["ts"] for e in evs) == 0
    assert {e["pid"] for e in evs} == {0}
    assert any(e["name"] == "dispatch.full" for e in evs)
    # Chrome object form round-trips
    back = json.loads(json.dumps(merged))
    assert back["displayTimeUnit"] == "ms"
    with pytest.raises(FileNotFoundError):
        trace_report.merge_runs([str(tmp_path)])  # no trace files here


# ---------------------------------------------------------------------------
# satellite: counter/doc lint
# ---------------------------------------------------------------------------

def _doc_text():
    text = ""
    for p in glob.glob(os.path.join(REPO, "docs", "tutorials", "*.md")):
        with open(p) as f:
            text += f.read()
    return text


def _literal_counters():
    names = set()
    pats = (os.path.join(REPO, "deepspeed_tpu", "**", "*.py"),
            os.path.join(REPO, "tools", "*.py"))
    for pat in pats:
        for p in glob.glob(pat, recursive=True):
            with open(p) as f:
                src = f.read()
            for m in re.finditer(r'COUNTERS\.add\(\s*f?"([^"{]+)"', src):
                names.add(m.group(1))
    return names


def test_every_counter_is_documented():
    """Every literal counter the code bumps appears in docs/tutorials/
    — by exact name, by family wildcard (`p2p.*`), or via the
    documented `*_logical` twin convention."""
    docs = _doc_text()
    names = _literal_counters()
    assert len(names) > 40, "counter extraction regressed"

    def documented(n):
        if f"`{n}`" in docs or n in docs:
            return True
        fam = n.split(".", 1)[0] + ".*"
        if f"`{fam}`" in docs:
            return True
        if n.endswith("_logical"):
            return documented(n[: -len("_logical")])
        return False

    undocumented = sorted(n for n in names if not documented(n))
    assert not undocumented, (
        f"counters bumped in code but absent from docs/tutorials/: "
        f"{undocumented} — document them (monitoring.md or tracing.md)")


def test_us_in_bytes_convention_is_documented():
    """Each counter in the µs-in-bytes set must be flagged as such near
    its doc mention — a reader of the comm table must not price these
    as wire traffic."""
    docs = _doc_text()
    lines = docs.splitlines()
    for name in US_IN_BYTES_COUNTERS:
        hits = [i for i, ln in enumerate(lines) if name in ln]
        assert hits, f"µs-convention counter {name} undocumented"
        flagged = any(
            re.search(r"µs|microsecond", " ".join(
                lines[max(0, i - 3):i + 4]), re.IGNORECASE)
            for i in hits)
        assert flagged, (f"{name} is in US_IN_BYTES_COUNTERS but its doc "
                         f"mention never says the bytes slot holds µs")


def test_trace_counters_excluded_from_comm_table():
    """The rendered exclusion itself is pinned end-to-end by
    tools/run_report.py --selftest (run in test_monitor); this lint
    keeps the exclusion tuple from losing the trace./slo. prefixes in
    a refactor without that selftest being updated in lockstep."""
    src_path = os.path.join(REPO, "deepspeed_tpu", "monitor", "report.py")
    with open(src_path) as f:
        src = f.read()
    m = re.search(r"wire_counters = \{.*?\}", src, re.DOTALL)
    assert m, "comm-table filter not found in report.py"
    assert '"trace."' in m.group(0) and '"slo."' in m.group(0)


# (the serve_bench --trace lane itself is exercised by run_dry in
# tests/test_serving.py, which now runs the continuous lane traced and
# asserts the trace parses with queue/prefill/decode spans)
