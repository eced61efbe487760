"""The three readers of the program's own phases and stamps (PR 41), on
traces built by hand — times in ns, worked out on paper — and on the
serving trace recorded before the program had any phase, where every one
of them has to find nothing."""

import os
import types

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr
from benchmarks.readers import (program_token_gaps, trace_host_phase,
                                trace_idle_under)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def _trace(busy, host, window=(0, 100 * MS)):
    ops = [tr.Event("op", a, b) for a, b in busy]
    dev = tr.Device(ops=ops, async_ops=[], modules=[], busy=tr.union(busy))
    return tr.Trace(window=window, devices={0: dev},
                    host=[tr.Event(n, a, b) for n, a, b in host])


def _run(spans=(), steps=0):
    return harness.RunResult(end_to_end={}, correct=True, attempted=1,
                             failed=0, notes=[], memory_peak_bytes=0,
                             program_spans=list(spans),
                             shapes={"steps_traced": steps})


def _idle(trace, run=None, **args):
    return trace_idle_under.read(cell=None, run=run or _run(), trace=trace,
                                 **args)


NO_WORK = dict(match=r"^serve\.idle$", present=r"^serve\.")
HOST = dict(match=r"^serve\.(?!idle$)", present=r"^serve\.")


def test_a_gap_split_between_two_phases_is_split_exactly():
    # busy 0-10 and 40-100 ms: one idle gap, 10-40.  The worker idles to
    # 25, then admits to 31 and launches to 45 (the device starts at 40).
    t = _trace([(0, 10 * MS), (40 * MS, 100 * MS)],
               [("serve.idle", 5 * MS, 25 * MS),
                ("serve.admit", 25 * MS, 31 * MS),
                ("serve.prefill.launch", 31 * MS, 45 * MS),
                ("bench.submit", 24 * MS, 26 * MS)])
    assert _idle(t, **NO_WORK) == pytest.approx(15.0)      # 10-25 of 100
    assert _idle(t, **HOST) == pytest.approx(15.0)         # 25-40
    # the reducer's "covers most" hands all 30 ms to the one `bench.*`
    # event that touches 2 of them, as in the ledger's lines
    assert dict(t.idle_gaps()) == {"bench.submit": pytest.approx(0.030)}


def test_a_gap_under_no_phase_is_nobodys_and_short_gaps_are_not_gaps():
    # idle 10-30 (under nothing of the engine's), 50-50.01 (10 us: not a
    # gap), 70-80 (the last 4 ms under serve.read)
    t = _trace([(0, 10 * MS), (30 * MS, 50 * MS),
                (50 * MS + 10_000, 70 * MS), (80 * MS, 100 * MS)],
               [("np.asarray(jax.Array)", 10 * MS, 30 * MS),
                ("serve.idle", 50 * MS, 50 * MS + 10_000),
                ("serve.read", 76 * MS, 90 * MS)])
    assert _idle(t, **NO_WORK) == 0.0       # present, and under no gap
    assert _idle(t, **HOST) == pytest.approx(4.0)
    assert t.idle_pct() == pytest.approx(30.01)  # the rest is nobody's


def test_a_nested_upload_is_counted_once_and_read_on_its_own():
    t = _trace([(0, 10 * MS), (20 * MS, 100 * MS)],
               [("serve.decode.launch", 12 * MS, 22 * MS),
                ("serve.decode.upload", 13 * MS, 16 * MS),
                ("serve.decode.launch", 50 * MS, 52 * MS),
                ("serve.decode.launch", 99 * MS, 104 * MS),
                ("serve.decode.launch", -3 * MS, 1 * MS)])
    assert _idle(t, **HOST) == pytest.approx(8.0)          # 12-20, once
    assert _idle(t, match=r"^serve\.decode\.upload$") == pytest.approx(3.0)
    read = lambda **kw: trace_host_phase.read(cell=None, run=_run(), trace=t,
                                              scale=1000.0, **kw)
    # the three that begin inside the window: 10, 2 and 5 ms
    assert read(name="serve.decode.launch", stat="median") == \
        pytest.approx(5.0)
    assert read(name="serve.decode.launch", stat="max") == pytest.approx(10.0)
    assert read(name="serve.decode.upload") == pytest.approx(3.0)
    assert read(name="serve.draft") is None


def test_training_idle_is_ms_a_traced_step():
    # four steps traced; the device idles 0-6 before the first launch and
    # 50-50.5 between two steps
    t = _trace([(6 * MS, 50 * MS), (50 * MS + 500_000, 100 * MS)],
               [("train.settle_flag", 0, 1 * MS),
                ("train.inputs", 1 * MS, 4 * MS),
                ("train.launch", 4 * MS, 7 * MS),
                ("train.inputs", 50 * MS + 100_000, 50 * MS + 300_000),
                ("bench.dispatch", 0, 8 * MS)])
    run = _run(steps=4)
    assert _idle(t, run, match=r"^train\.inputs$", per="step") == \
        pytest.approx(3.2 / 4)
    assert _idle(t, run, match=r"^train\.(launch|settle_flag)$",
                 per="step") == pytest.approx(3.0 / 4)
    assert _idle(t, _run(), match=r"^train\.inputs$", per="step") is None


def _stamped():
    """Two requests: rid 0 at 0, 6, 12, 90 ms (its last gap holds rid 1's
    prefill chunk), rid 1 at 84, 90 ms."""
    step = lambda t, rids: {"ph": "X", "name": "decode_step", "ts": t - 7000,
                            "dur": 7500, "args": {"rids": rids,
                                                  "stamp_us": t, "batch": 2}}
    first = lambda t, rid: {"ph": "i", "name": "first_token", "ts": t + 3,
                            "args": {"rid": rid, "stamp_us": t}}
    return [first(0, 0), step(6000, [0]), step(12000, [0]),
            {"ph": "X", "name": "prefill_chunk", "ts": 13000, "dur": 500,
             "args": {"rid": 1}},
            first(84000, 1), step(90000, [0, 1]),
            {"ph": "X", "name": "queue_wait", "ts": 12500, "dur": 100}]


def test_token_gaps_by_rid_and_the_share_that_hold_a_chunk():
    read = lambda stat: program_token_gaps.read(
        cell=None, run=_run(_stamped()), trace=None, stat=stat)
    assert sorted(b - a for a, b in program_token_gaps.gaps_us(_stamped())) \
        == [6000, 6000, 6000, 78000]
    assert read("median") == pytest.approx(6.0)
    assert read("p95") == pytest.approx(78.0)
    assert read("chunk_share") == pytest.approx(25.0)      # one gap of four
    # the parent's events carry no stamp: nothing to read
    bare = [dict(e, args={k: v for k, v in e.get("args", {}).items()
                          if k not in ("stamp_us", "rids")})
            for e in _stamped()]
    for stat in ("median", "p95", "chunk_share"):
        assert program_token_gaps.read(cell=None, run=_run(bare), trace=None,
                                       stat=stat) is None


def test_the_recorded_serving_trace_has_no_phase_so_every_reader_finds_nothing():
    serve = tr.reduce_file(os.path.join(DATA, "serve_small.xplane.pb.gz"))
    assert not any(e.name.startswith(("serve.", "train."))
                   for e in serve.host)
    run = _run(steps=4)
    for f in sorted(os.listdir(os.path.join(harness.BENCH, "layer_metrics"))):
        spec = harness.load_json("layer_metrics", f)
        if spec["reader"] not in ("trace_host_phase", "trace_idle_under",
                                  "program_token_gaps"):
            continue
        got = harness.plugin("readers", spec["reader"]).read(
            cell=types.SimpleNamespace(), run=run, trace=serve,
            **spec["args"])
        assert got is None, f
