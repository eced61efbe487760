"""`readers/trace_scope_time.py` (PR 59) on a trace built by hand — times
in ns, worked out on paper — and on the serving trace recorded on the
chip with the engine's `program_scopes` events beside it
(`data/record_scoped_trace.py`)."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr
from benchmarks.readers import trace_scope_time

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1_000

PATHS = ["", "attn", "attn/swa_attend/oracle.grouped_attention",
         "attn/swa_attend/oracle.grouped_attention/while/body",
         "ffn/moe_experts/kernel.touched_experts", "ffn/moe_experts", "head",
         "scratch/thing"]
DECODE = {"fusion.1": 1, "while.4": 2, "dot.7": 3, "fusion.8": 5,
          "custom-call.2": 4, "add.6": 7}
PREFILL = {"fusion.1": 5, "sort.3": 6}


def _scopes(program, instructions):
    return {"ph": "i", "name": "program_scopes", "cat": "serve", "ts": 0,
            "args": {"program": program, "paths": PATHS, "seconds": 0.1,
                     "instructions": instructions}}


def _op(name, a, b):
    return tr.Event(f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop",
                    a * US, b * US)


def _trace():
    """Window 0-1000 us.  `jit_decode` runs 100-300 (A), 500-700 (B) and
    950-1050 (C: over the window's end, not a run of the window);
    `jit_prefill` 320-480; `jit_other`, which recorded no map, 720-760.

    A: fusion.1 100-140 attn; while.4 140-240 attn, and inside it dot.7
    150-170 (its body's: attn), copy.9 170-180 (the map lacks it: the
    while's) and fusion.8 180-200 (ffn: an inner event with a scope of
    its own); custom-call.2 240-280 ffn; copy-start.21 280-290 (the map
    lacks it); add.6 290-295 (a path under none of the stages).
    B: fusion.1 500-560 attn; custom-call.2 560-700 ffn.
    prefill: fusion.1 320-400 — ITS map says ffn — and sort.3 400-480
    head.  other: fusion.1 720-760.  copy.77 800-820 under no run.  C:
    fusion.1 950-1000 (clipped)."""
    ops = [_op("fusion.1", 100, 140), _op("while.4", 140, 240),
           _op("dot.7", 150, 170), _op("copy.9", 170, 180),
           _op("fusion.8", 180, 200), _op("custom-call.2", 240, 280),
           _op("copy-start.21", 280, 290), _op("add.6", 290, 295),
           _op("fusion.1", 320, 400), _op("sort.3", 400, 480),
           _op("fusion.1", 500, 560), _op("custom-call.2", 560, 700),
           _op("fusion.1", 720, 760), _op("copy.77", 800, 820),
           _op("fusion.1", 950, 1000)]
    mod = lambda name, a, b: tr.Event(f"{name}(123)", a * US, b * US)
    modules = [mod("jit_decode", 100, 300), mod("jit_prefill", 320, 480),
               mod("jit_decode", 500, 700), mod("jit_other", 720, 760),
               mod("jit_decode", 950, 1050)]
    dev = tr.Device(ops=ops, async_ops=[], modules=modules,
                    busy=tr.union((e.start, e.end) for e in ops))
    return tr.Trace(window=(0, 1000 * US), devices={0: dev}, host=[])


def _run(spans):
    return harness.RunResult(end_to_end={}, correct=True, attempted=1,
                             failed=0, notes=[], memory_peak_bytes=0,
                             program_spans=list(spans))


BOTH = [_scopes("jit_decode", DECODE), _scopes("jit_prefill", PREFILL)]


def _read(spans=BOTH, trace=None, **args):
    return trace_scope_time.read(cell=None, run=_run(spans),
                                 trace=trace or _trace(), **args)


def test_a_step_by_stage_each_nanosecond_once():
    stage = lambda s: _read(program="jit_decode", match=f"^{s}(/|$)")
    # two runs: A 40 + (100 - 20) and B 60 of attn; A 20 + 40 and B 140
    # of ffn; A's 10 + 5 under no stage
    assert stage("attn") == pytest.approx((120 + 60) / 2 / 1000)   # ms
    assert stage("ffn") == pytest.approx((60 + 140) / 2 / 1000)
    assert stage("state") == 0.0
    assert _read(program="jit_decode", match="^$") == \
        pytest.approx(15 / 2 / 1000)
    # a kernel by its name, wherever it lies; another unit
    assert _read(program="jit_decode", scale=1e6,
                 match=r"(^|/)kernel\.touched_experts(/|$)") == \
        pytest.approx((40 + 140) / 2)
    assert _read(program="jit_decode", match="(^|/)moe_experts(/|$)") == \
        pytest.approx(0.1)
    # stages and the rest sum to the operations' time: A 195 of 200 us
    # (it idles 295-300), B all 200
    parts = [stage(s) for s in trace_scope_time.STAGES] + \
        [_read(program="jit_decode", match="^$")]
    assert sum(parts) == pytest.approx(395 / 2 / 1000)


def test_each_program_reads_its_own_map():
    # prefill's fusion.1 is ffn by prefill's map, attn by decode's
    assert _read(program="jit_prefill", match="^ffn(/|$)") == \
        pytest.approx(0.080)
    assert _read(program="jit_prefill", match="^attn(/|$)") == 0.0
    assert _read(program="jit_prefill", match="^(embed|head|sample)(/|$)"
                 ) == pytest.approx(0.080)


def test_share_of_the_busy_time_no_stage_owns():
    # busy: A 195, prefill 160, B 200, other 40, stray 20, C 50 = 665 us;
    # no stage: A's 15, the program without a map, the stray copy and the
    # run over the window's end
    assert _read(match="^$", per="busy") == pytest.approx(
        100 * (15 + 40 + 20 + 50) / 665)
    assert _read(match="^attn(/|$)", per="busy") == pytest.approx(
        100 * 180 / 665)
    assert _read(match="^ffn(/|$)", per="busy", program="jit_prefill") == \
        pytest.approx(100 * 80 / 665)
    # with prefill's map alone, decode's runs are nobody's
    assert _read([BOTH[1]], match="^$", per="busy") == pytest.approx(
        100 * (665 - 160) / 665)


def test_nothing_to_read_reads_nothing():
    assert trace_scope_time.read(cell=None, run=_run(BOTH), trace=None,
                                 program="jit_decode", match="^attn") is None
    assert _read([], program="jit_decode", match="^attn") is None
    assert _read([], match="^$", per="busy") is None
    assert _read([BOTH[1]], program="jit_decode", match="^attn") is None
    assert _read([_scopes("jit_verify", DECODE)], program="jit_verify",
                 match="^attn") is None     # it did not run in the window
    # the parent's run: spans, and no `program_scopes` among them
    assert _read([{"ph": "X", "name": "decode_step", "cat": "serve",
                   "ts": 0, "dur": 5, "args": {"rids": [1]}}],
                 program="jit_decode", match="^attn") is None


# -- the trace recorded on the chip -----------------------------------------


@pytest.fixture(scope="module")
def recorded():
    trace = tr.reduce_file(os.path.join(DATA, "serve_scoped.xplane.pb.gz"))
    with open(os.path.join(DATA, "serve_scoped.scopes.json")) as f:
        return trace, _run(json.load(f))


def _recorded(recorded, **args):
    trace, run = recorded
    return trace_scope_time.read(cell=None, run=run, trace=trace, **args)


def test_recorded_decode_stages_sum_to_its_runs_device_time(recorded):
    trace, run = recorded
    assert {e["args"]["program"] for e in run.program_spans} == \
        {"jit_prefill", "jit_decode", "jit_seat"}
    for program in ("jit_decode", "jit_prefill"):
        runs = trace.module_durations(program)
        assert len(runs) >= 3
        stages = {s: _recorded(recorded, program=program,
                               match=f"^{s}(/|$)")
                  for s in trace_scope_time.STAGES}
        assert stages["state"] == 0.0 and all(
            v > 0 for s, v in stages.items() if s != "state"), stages
        rest = _recorded(recorded, program=program, match="^$")
        mean_ms = 1e3 * sum(runs) / len(runs)
        assert sum(stages.values()) + rest == pytest.approx(mean_ms,
                                                            rel=0.02)
        assert rest < 0.05 * mean_ms


def test_recorded_kernel_is_read_by_its_name(recorded):
    # the decode step's paged attention is the Mosaic kernel: one call a
    # layer, under `attn/paged_attend/kernel.paged_attention`
    kernel = _recorded(recorded, program="jit_decode",
                       match=r"(^|/)kernel\.paged_attention(/|$)")
    attn = _recorded(recorded, program="jit_decode", match="^attn(/|$)")
    assert 0 < kernel < attn
    assert _recorded(recorded, program="jit_prefill",
                     match=r"(^|/)kernel\.") == 0.0
    assert _recorded(recorded, match="^$", per="busy") < 5.0


SCOPED = sorted(
    f[:-5] for f in os.listdir(os.path.join(harness.BENCH, "layer_metrics"))
    if harness.load_json("layer_metrics", f)["reader"] == "trace_scope_time")


@pytest.mark.parametrize("metric", SCOPED)
def test_each_metric_file_reads_a_number_on_the_recorded_trace(recorded,
                                                               metric):
    """The files' own `args`: a float where the program ran (0.0 where a
    GPT has no such scope), a share of the busy time under 100."""
    spec = harness.load_json("layer_metrics", metric + ".json")
    got = _recorded(recorded, **spec["args"])
    assert isinstance(got, float) and got >= 0.0
    gpt_has = not any(s in metric for s in ("state", "moe", "dsa"))
    assert (got > 0.0) == gpt_has, got
    if spec["unit"] == "%":
        assert got < 100.0


def test_recorded_compiler_made_movement_is_a_share_of_the_busy_time(
        recorded):
    """`scope_moved_pct.serve`: what the stages hold only because the map
    gives a compiler-made instruction its first consumer's path."""
    trace, _ = recorded
    spec = harness.load_json("layer_metrics", "scope_moved_pct.serve.json")
    assert len(SCOPED) == 11 and spec["args"]["per"] == "busy"
    share = _recorded(recorded, **spec["args"])
    moved_ms = sum(
        _recorded(recorded, program=p, match=spec["args"]["match"])
        * len(trace.module_durations(p))
        for p in ("jit_prefill", "jit_decode", "jit_seat"))
    busy_ms = sum(tr.total(d.busy) for d in trace.devices.values()) / 1e6
    assert share == pytest.approx(100.0 * moved_ms / busy_ms, rel=1e-6)
    # beside it, what no stage owns at all
    assert 5.0 < share < 60.0
    assert _recorded(recorded, match="^$", per="busy") < 5.0
