"""The trace reduction on two small traces recorded on the v5e
(tests/data/record_trace.py, PR 24): two training steps of a 2-layer GPT,
and ten serving steps.  The expected values were worked out from the
events by hand (tests/data/README.md) and by a second, naive method."""

import os

import numpy as np
import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def train():
    return tr.reduce_file(os.path.join(DATA, "train_small.xplane.pb.gz"))


@pytest.fixture(scope="module")
def serve():
    return tr.reduce_file(os.path.join(DATA, "serve_small.xplane.pb.gz"))


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == \
        [(1, 4), (5, 8)]
    assert tr.total([(1, 4), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(1, 4), (5, 8)]) == \
        [(0, 1), (4, 5), (8, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(2, 7)]) == [(0, 2), (7, 9)]
    assert tr.gaps([(1, 4)], 0, 5) == [(0, 1), (4, 5)]


def test_short_names():
    hlo = ('%fusion.2 = bf16[1024,256]{1,0:T(8,128)(2,1)S(1)} fusion(bf16['
           '1024,256]{1,0} %x), kind=kCustom, calls=%fused_computation.2')
    assert tr.short_name(hlo) == "fusion:kCustom bf16[1024,256]"
    call = ('%jvp__.3 = (bf16[8,256,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[8,256,'
            '128]{2,1,0:T(8,128)S(1)}) custom-call(s32[2]{0} %b), '
            'custom_call_target="tpu_custom_call"')
    assert tr.short_name(call) == \
        "custom-call:tpu_custom_call (bf16[8,256,64], f32[8,256,128])"
    assert tr.module_name("jit_full_step(9147376617240341932)") == \
        "jit_full_step"


def test_training_trace_by_hand(train):
    # no bench.window in this recording: the window is the span of the
    # device's programs and operations, 67,960,102 ns to 75,764,014 ns
    assert train.window == (67960102, 75764014)
    assert train.window_s == pytest.approx(0.007803912)
    # two runs of the fused step, 261,918 ns and 262,241 ns on the device
    assert train.module_durations("jit_full_step") == \
        pytest.approx([261.918e-6, 262.241e-6])
    # twelve flash kernel events: (forward, dq, dkv) x 2 layers x 2 steps
    flash = 'custom_call_target="tpu_custom_call"'
    assert train.matching_op_count(flash) == 12
    assert train.matching_op_seconds(flash) == pytest.approx(147.829e-6)
    assert train.collective_s() == 0
    # the host dispatches for 4.8 ms a step and the device works 0.26 ms
    assert train.busy_s == pytest.approx(491.73e-6, rel=1e-4)
    assert train.idle_pct() == pytest.approx(93.69893, abs=1e-4)
    (name, seconds), = train.idle_gaps()
    assert name == "bench.dispatch"
    assert seconds == pytest.approx(7.283e-3, rel=1e-3)
    top = train.breakdown()["device_ops"]
    assert len(top) == 10 and top[0][0].startswith(
        "custom-call:tpu_custom_call (bf16[8,256,64], f32[8,256,128])")
    assert top[0][1] == pytest.approx(59.602e-6)


def test_busy_union_against_a_naive_timeline(train):
    """A second method: paint every operation onto a boolean timeline of
    one cell a nanosecond and count."""
    dev = train.devices[0]
    lo, hi = train.window
    painted = np.zeros(hi - lo, bool)
    for e in dev.ops:
        painted[e.start - lo:e.end - lo] = True
    assert int(painted.sum()) == tr.total(dev.busy)
    assert tr.total(tr.gaps(dev.busy, lo, hi)) == int((~painted).sum())


def test_serving_trace_by_hand(serve):
    # seven decode steps and three prefill chunks ran
    assert len(serve.module_durations("jit_decode")) == 7
    assert len(serve.module_durations("jit_prefill")) == 3
    assert np.median(serve.module_durations("jit_decode")) == \
        pytest.approx(68.058e-6)
    assert sum(serve.module_durations("jit_prefill")) == \
        pytest.approx(174.974e-6, rel=1e-4)
    assert serve.idle_pct() == pytest.approx(97.69524, abs=1e-4)
    # nothing of the benchmark's own covers the gaps: the engine's loop
    # is not annotated yet, so JAX's own host events name them
    names = [n for n, _ in serve.idle_gaps()]
    assert names and all(n.startswith("unannotated:") for n in names)
    assert names[0] == "unannotated:np.asarray(jax.Array)"


def test_a_window_clips_the_events(train):
    lo = 70407573            # the first fused step starts here
    t = tr.reduce(tr.load(os.path.join(DATA, "train_small.xplane.pb.gz")),
                  window=(lo, lo + 261918))
    assert t.window_s == pytest.approx(261.918e-6)
    assert t.module_durations("jit_full_step") == pytest.approx([261.918e-6])
    assert t.matching_op_count('custom_call_target="tpu_custom_call"') == 6
    assert t.idle_pct() < 12


def test_collectives_and_their_exposed_part():
    ev = tr.Event
    dev = tr.Device(
        ops=[ev("%f = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 0, 10),
             ev("%ar = f32[8]{0} all-reduce(f32[8]{0} %f)", 10, 30),
             ev("%g = f32[8]{0} fusion(f32[8]{0} %ar), kind=kLoop", 40, 50)],
        async_ops=[ev("%ag = (f32[8]{0}) all-gather-start(f32[2]{0} %x)",
                      35, 45)],
        modules=[], busy=tr.union([(0, 30), (40, 50)]))
    t = tr.Trace(window=(0, 100), devices={0: dev}, host=[])
    assert t.collective_s() == pytest.approx(30e-9)      # 10-30 and 35-45
    assert t.collective_exposed_s() == pytest.approx(25e-9)  # less 40-45
