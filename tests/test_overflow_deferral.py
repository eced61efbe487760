"""The hot path keeps the newest overflow flag in flight (engine.
_resolve_pending_overflow(keep_newest=True)) and the step after it picks
its learning rate in the program (step_builder.StepLR).  Held here: the
result is bit for bit the one a blocking settle before every dispatch
gives, and the hot path never waits for a flag."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.runtime.step_builder import StepLR, select_lr
from tests.simple_model import SimpleModel, random_batches

STEPS = 8
OVERFLOW_AT = (0, 3, 4)  # the first step, then two in a row

CASES = {  # name -> (gas, scheduler, train_batch's one-program scan)
    "gas1_fused": (1, True, False),
    "gas2_split": (2, True, False),
    "gas2_scan": (2, True, True),
    "no_scheduler": (1, False, False),
}


def config(gas, scheduler, **over):
    cfg = {
        "train_batch_size": 32,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "fp16": {"enabled": True, "loss_scale": 0,
                 "initial_scale_power": 8, "hysteresis": 1,
                 "loss_scale_window": 2},
        "steps_per_print": 0,
    }
    if scheduler:
        cfg["scheduler"] = {"type": "WarmupLR", "params": {
            "warmup_min_lr": 1e-3, "warmup_max_lr": 2e-2,
            "warmup_num_steps": 6}}
    cfg.update(over)
    return cfg


def global_batches(gas, steps=STEPS, overflow_at=OVERFLOW_AT):
    """`steps` global batches of `gas` micro batches; an inf in the last
    micro batch of the steps named."""
    micro = list(random_batches(steps * gas, batch_size=32 // gas, seed=7))
    out = [micro[i * gas:(i + 1) * gas] for i in range(steps)]
    for i in overflow_at:
        if i < steps:
            x, y = out[i][-1]
            x = x.copy()
            x[0, 0] = np.inf
            out[i][-1] = (x, y)
    return out


class Recorder:
    """What each step was dispatched with and left behind, as device
    values: nothing here reads one before the run is over."""

    def __init__(self, engine):
        self.engine, self.lrs, self.scales = engine, [], []
        inner = engine._step_lr

        def step_lr():
            lr = inner()
            self.lrs.append(lr)
            return lr

        engine._step_lr = step_lr

    def after_step(self):
        # a copy: the apply program donates the scaler state it is handed
        self.scales.append(self.engine._scaler_state["cur_scale"] + 0)

    def applied_lrs(self):
        return [None if lr is None else float(select_lr(lr))
                for lr in self.lrs]


def run(engine, batches, scan=False, blocking=False, rec=None):
    """Drive `batches`; `blocking` settles every flag before every
    dispatch, which is what the engine did before the flag stayed in
    flight."""
    settle = engine._resolve_pending_overflow if blocking else (lambda: None)
    for micro in batches:
        if scan:
            settle()
            engine.train_batch(iter(micro))
        else:
            for b in micro:
                settle()
                engine.forward(b)
                engine.backward()
                settle()
                engine.step()
        if rec is not None:
            rec.after_step()


def state(engine):
    """Settled: `skipped_steps` resolves every flag first."""
    sched = engine.lr_scheduler
    return {
        "skipped_steps": engine.skipped_steps,
        "global_steps": engine.global_steps,
        "cur_scale": float(engine._scaler_state["cur_scale"]),
        "last_batch_iteration": getattr(sched, "last_batch_iteration", None),
        "lr": engine._current_lr(),
    }


def assert_same_bits(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", list(CASES))
def test_deferred_flag_is_exact(case):
    gas, scheduler, scan = CASES[case]
    deferred, *_ = ds.initialize(model=SimpleModel(),
                                 config=config(gas, scheduler))
    blocking, *_ = ds.initialize(model=SimpleModel(),
                                 config=config(gas, scheduler))
    assert ("full" in deferred._step_fns) == (gas == 1)
    rec_d, rec_b = Recorder(deferred), Recorder(blocking)
    batches = global_batches(gas)
    run(deferred, batches, scan=scan, rec=rec_d)
    in_flight = len(deferred._pending_overflow)
    run(blocking, batches, scan=scan, blocking=True, rec=rec_b)

    assert in_flight == 2  # the last step's, and the one before it
    assert len(rec_d.lrs) == len(rec_b.lrs) == STEPS
    # the blocking loop never hands a program a live flag ...
    assert all(not bool(lr.prev_overflow) for lr in rec_b.lrs)
    # ... the deferred one does, after each overflowed step
    assert [bool(lr.prev_overflow) for lr in rec_d.lrs] == \
        [i - 1 in OVERFLOW_AT for i in range(STEPS)]
    assert rec_d.applied_lrs() == rec_b.applied_lrs()
    if scheduler:  # the rate moves every applied step of the warm-up
        assert len(set(rec_b.applied_lrs())) >= STEPS - len(OVERFLOW_AT)
    assert [float(s) for s in rec_d.scales] == \
        [float(s) for s in rec_b.scales]
    assert state(deferred) == state(blocking)
    assert deferred.skipped_steps == len(OVERFLOW_AT)
    assert_same_bits(deferred.params, blocking.params)
    assert_same_bits(deferred._opt_state, blocking._opt_state)


def test_rate_stays_none_where_the_optimizer_shows_none(monkeypatch):
    """An optimizer that shows no rate (no torch-style param_groups) is
    handed lr=None, its own default, flag in flight or not."""
    engine, *_ = ds.initialize(model=SimpleModel(), config=config(1, False))
    monkeypatch.setattr(engine, "_current_lr", lambda: None)
    rec = Recorder(engine)
    run(engine, global_batches(1, steps=3, overflow_at=(1,)))
    assert rec.lrs == [None, None, None]
    assert engine.skipped_steps == 1


@pytest.mark.parametrize("reload_into", ["fresh_engine", "same_engine"])
def test_checkpoint_between_overflow_and_settlement(tmp_path, reload_into):
    """Step 3 overflows; the save right after it finds its flag in
    flight, settles it, and a resume from there ends where an
    uninterrupted blocking run does."""
    batches = global_batches(1)
    reference, *_ = ds.initialize(model=SimpleModel(), config=config(1, True))
    run(reference, batches, blocking=True)

    engine, *_ = ds.initialize(model=SimpleModel(), config=config(1, True))
    run(engine, batches[:4])
    assert len(engine._pending_overflow) == 2
    engine.save_checkpoint(str(tmp_path), tag="mid")
    assert engine._pending_overflow == []
    settled = dict(skipped=engine._skipped_steps,
                   it=engine.lr_scheduler.last_batch_iteration)
    assert settled == dict(skipped=2, it=1)  # 4 steps, 2 of them applied

    if reload_into == "fresh_engine":
        run(engine, batches[4:])  # the saver itself goes on unharmed
        assert state(engine) == state(reference)
        assert_same_bits(engine.params, reference.params)
        engine, *_ = ds.initialize(model=SimpleModel(),
                                   config=config(1, True))
    else:
        run(engine, batches[4:5])  # overflows; its flag is in flight
        assert len(engine._pending_overflow) == 1
    engine.load_checkpoint(str(tmp_path), tag="mid")
    assert engine._pending_overflow == []  # not the loaded state's flags
    assert engine._skipped_steps == 2
    assert engine.lr_scheduler.last_batch_iteration == 1
    run(engine, batches[4:])
    assert state(engine) == state(reference)
    assert_same_bits(engine.params, reference.params)


def watch_settles(engine, monkeypatch):
    """Fail the hot path if it is handed a flag the device has not
    produced; count what each kind of settle saw."""
    seen = {"hot": 0, "full": 0}
    inner_settle = engine._settle_overflow
    inner_resolve = engine._resolve_pending_overflow
    mode = []

    def settle(flag, step):
        kind = mode[-1]
        if kind == "hot":
            assert flag.is_ready(), "the hot path waited for a flag"
        seen[kind] += 1
        return inner_settle(flag, step)

    def resolve(keep_newest=False):
        mode.append("hot" if keep_newest else "full")
        try:
            return inner_resolve(keep_newest)
        finally:
            mode.pop()

    monkeypatch.setattr(engine, "_settle_overflow", settle)
    monkeypatch.setattr(engine, "_resolve_pending_overflow", resolve)
    return seen


def waits():
    return COUNTERS.totals().get("engine.overflow_flag.waits",
                                 {"calls": 0})["calls"]


@pytest.mark.parametrize("gas", [1, 2])
def test_hot_path_never_waits_for_a_flag(gas, monkeypatch, tmp_path):
    engine, *_ = ds.initialize(model=SimpleModel(), config=config(gas, True))
    seen = watch_settles(engine, monkeypatch)
    before, pending = waits(), None
    for micro in global_batches(gas, steps=6, overflow_at=()):
        for b in micro:
            loss = engine.forward(b)
            engine.backward()
            engine.step()
        # as a training loop does: step k is dispatched, then what step
        # k-1 left is read — from every device here (float() fetches one
        # shard, and the other virtual devices may still be on step k-1),
        # and the update's own output beside the loss (with gas 2 the
        # loss is an earlier program's than the flag)
        if pending is not None:
            jax.block_until_ready(pending)
        pending = (loss, engine._pending_overflow[-1][0])
    assert waits() == before
    # steps 2..5 each settled the flag of the step two back, none older
    assert seen == {"hot": 4, "full": 0}
    assert len(engine._pending_overflow) == 2  # steps 4 and 5
    assert engine._skipped_steps == 0

    # the callers that must see settled counters still settle everything
    assert engine.skipped_steps == 0
    assert seen == {"hot": 4, "full": 2} and engine._pending_overflow == []
    for b in global_batches(gas, steps=1, overflow_at=(0,))[0]:
        engine.forward(b)
        engine.backward()
        engine.step()
    engine.save_checkpoint(str(tmp_path), tag="t")
    assert engine._pending_overflow == [] and engine._skipped_steps == 1
    assert waits() == before


def test_monitored_engine_settles_every_step(tmp_path):
    """With a monitor attached each step already syncs, so each step's
    own flag is settled before its scalars are written."""
    cfg = config(1, True, tensorboard={"enabled": True,
                                       "output_path": str(tmp_path),
                                       "job_name": "t"})
    engine, *_ = ds.initialize(model=SimpleModel(), config=cfg)
    assert engine.monitor is not None
    for i, micro in enumerate(global_batches(1, steps=4, overflow_at=(1,))):
        engine.forward(micro[0])
        engine.backward()
        engine.step()
        assert engine._pending_overflow == []
        assert engine._skipped_steps == (1 if i >= 1 else 0)
    assert engine.lr_scheduler.last_batch_iteration == 2


def test_hot_path_counts_a_flag_it_had_to_wait_for():
    """`engine.overflow_flag.waits`: calls = flags found not ready,
    bytes slot = microseconds waited."""

    class Unready:
        waited = False

        def is_ready(self):
            return False

        def block_until_ready(self):
            self.waited = True

        def __bool__(self):
            assert self.waited
            return False

    engine, *_ = ds.initialize(model=SimpleModel(), config=config(1, False))
    flag = Unready()
    engine._pending_overflow[:] = [(flag, 1), (jnp.zeros((), bool), 2)]
    before = waits()
    engine._resolve_pending_overflow(keep_newest=True)
    assert flag.waited and waits() == before + 1
    assert len(engine._pending_overflow) == 1


def test_select_lr_forms():
    rates = jnp.asarray([0.25, 0.5], jnp.float32)
    assert float(select_lr(StepLR(rates, jnp.asarray(False)))) == 0.25
    assert float(select_lr(StepLR(rates, jnp.asarray(True)))) == 0.5
    assert select_lr(None) is None
    assert float(select_lr(jnp.asarray(0.125))) == 0.125
