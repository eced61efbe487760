"""Serving engine (deepspeed_tpu/serving): batching invariance, paged-KV
fragmentation, chaos shed, and the serve-bench tier-1 lanes.

THE acceptance pin: continuous-batched decode is token-identical to the
one-request-at-a-time oracle — greedy AND seeded-sampling — across
batch join/leave and KV block reuse.  Every program operation is
row-wise by construction (programs.py), so the identity is exact, not
tolerance-based."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import GPT, gpt2_config
from deepspeed_tpu.models.generation import generate, kth_largest
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (ERROR, FINISHED, TRASH_BLOCK, ServeConfig,
                                   ServeEngine, ServeProgramBuilder,
                                   ServeSchedule, WAITING)
from deepspeed_tpu.serving import programs as programs_mod
from deepspeed_tpu.serving.programs import sample_rows, top_k_filter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

VOCAB = 64
MAX_SEQ = 64
BS = 4            # KV block size
WIDTH = MAX_SEQ // BS


@pytest.fixture(scope="module")
def model_and_params():
    model = GPT(gpt2_config("nano", num_layers=2, num_heads=4, d_model=32,
                            vocab_size=VOCAB, max_seq_len=MAX_SEQ))
    return model, model.init(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def programs(model_and_params):
    """ONE compiled (prefill, decode) pair shared by every engine in
    this module — engines differ only in allocator/scheduler state."""
    model, _ = model_and_params
    sched = ServeSchedule(max_batch=4, prefill_chunk=8, block_size=BS,
                          num_blocks=40, table_width=WIDTH)
    return ServeProgramBuilder(model, sched).build()


def _cfg(**over):
    base = dict(block_size=BS, num_blocks=40, max_batch=4,
                prefill_chunk=8, max_seq_len=MAX_SEQ)
    base.update(over)
    return ServeConfig(**base)


def _engine(model_and_params, programs=None, **over):
    model, params = model_and_params
    return ServeEngine(model, params, _cfg(**over), programs=programs)


def _prompts(seed=0, lens=(5, 9, 3, 12)):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, VOCAB, (n,)).tolist() for n in lens]


def _alone(model_and_params, programs, prompt, n, **kw):
    """The one-request-at-a-time oracle: a fresh engine, one request."""
    eng = _engine(model_and_params, programs)
    return eng.generate([prompt], n, **kw)[0]


# -- the acceptance pins ----------------------------------------------------


def test_greedy_matches_generate_exactly(model_and_params, programs):
    """Serving greedy == models/generation.generate token for token
    (same cache length, whole prompt in one chunk: the programs mirror
    _block_with_cache op for op)."""
    model, params = model_and_params
    prompt = _prompts()[0]
    got = _alone(model_and_params, programs, prompt, 10)
    want = np.asarray(generate(model, params,
                               np.asarray([prompt], np.int32), 10,
                               cache_len=WIDTH * BS))[0].tolist()
    assert got == want


def test_greedy_alone_static_and_midflight_are_token_identical(
        model_and_params, programs):
    prompts = _prompts()
    oracle = [_alone(model_and_params, programs, p, 8) for p in prompts]

    # static batch: all submitted before any step
    eng = _engine(model_and_params, programs)
    batch = eng.generate(prompts, 8)
    assert batch == oracle

    # continuous: requests join mid-flight at staggered decode steps
    eng = _engine(model_and_params, programs)
    r0 = eng.submit(prompts[0], 8)
    for _ in range(3):
        eng.step()
    r1 = eng.submit(prompts[1], 8)
    eng.step()
    r2 = eng.submit(prompts[2], 8)
    for _ in range(2):
        eng.step()
    r3 = eng.submit(prompts[3], 8)
    eng.run()
    assert [r.out for r in (r0, r1, r2, r3)] == oracle
    assert all(r.state == FINISHED for r in (r0, r1, r2, r3))


def test_sampled_identical_under_seed_across_join_leave(
        model_and_params, programs):
    """Temperature/top-k sampling: the RNG key is a pure function of
    (request seed, position) — batch composition can never reach it."""
    prompts = _prompts(seed=3)
    kw = dict(temperature=0.8, top_k=5)
    oracle = []
    for i, p in enumerate(prompts):
        eng = _engine(model_and_params, programs)
        r = eng.submit(p, 8, seed=100 + i, **kw)
        eng.run()
        oracle.append(r.out)
    # tokens must actually vary (a collapsed distribution would make
    # the invariance pin vacuous)
    assert any(len(set(o)) > 1 for o in oracle)

    eng = _engine(model_and_params, programs)
    r0 = eng.submit(prompts[0], 8, seed=100, **kw)
    for _ in range(2):
        eng.step()
    r1 = eng.submit(prompts[1], 8, seed=101, **kw)
    r2 = eng.submit(prompts[2], 8, seed=102, **kw)
    for _ in range(3):
        eng.step()
    r3 = eng.submit(prompts[3], 8, seed=103, **kw)
    eng.run()
    assert [r.out for r in (r0, r1, r2, r3)] == oracle


def test_mixed_greedy_and_sampled_requests_in_one_batch(
        model_and_params, programs):
    prompts = _prompts(seed=5)
    greedy_oracle = _alone(model_and_params, programs, prompts[0], 6)
    sampled_oracle = _alone(model_and_params, programs, prompts[1], 6,
                            temperature=1.0, top_k=0, seeds=[7])
    eng = _engine(model_and_params, programs)
    rg = eng.submit(prompts[0], 6)
    rs_ = eng.submit(prompts[1], 6, temperature=1.0, seed=7)
    eng.run()
    assert rg.out == greedy_oracle
    assert rs_.out == sampled_oracle


# -- paged-KV allocator / fragmentation -------------------------------------


def test_block_free_realloc_decode_fragmentation(model_and_params,
                                                 programs):
    """The fragmentation pin: blocks freed by a finished request are
    REUSED by later requests (LIFO free list), and decode through the
    recycled (stale-content) blocks is still token-identical."""
    prompts = _prompts(seed=8)
    eng = _engine(model_and_params, programs, num_blocks=9)  # 8 usable
    r0 = eng.submit(prompts[0], 6)
    eng.step()
    blocks0 = set(eng.kv.blocks_of(r0.rid))
    assert blocks0 and TRASH_BLOCK not in blocks0
    eng.run()
    assert eng.kv.blocks_in_use == 0
    oracle0 = list(r0.out)

    # two new requests re-occupy the just-freed physical blocks
    r1 = eng.submit(prompts[1], 6)
    r2 = eng.submit(prompts[0], 6)
    eng.step()  # admission happens in the first step
    used = set(eng.kv.blocks_of(r1.rid)) | set(eng.kv.blocks_of(r2.rid))
    assert used & blocks0, "free list must recycle r0's blocks"
    eng.run()
    assert TRASH_BLOCK not in used
    # same outputs through recycled (stale-content) blocks as fresh ones
    assert r2.out == oracle0
    assert r1.out == _alone(model_and_params, programs, prompts[1], 6)
    assert eng.kv.blocks_in_use == 0 and eng.kv.evictions == 0


def test_kv_exhaustion_queues_instead_of_erroring(model_and_params,
                                                  programs):
    """More demand than blocks: later requests WAIT for frees (FIFO),
    everything completes, occupancy never exceeds capacity."""
    prompts = _prompts(seed=11, lens=(6, 6, 6, 6))
    eng = _engine(model_and_params, programs, num_blocks=7)  # 6 usable
    # each request: ceil((6 + 6) / 4) = 3 blocks -> two fit at once
    reqs = [eng.submit(p, 6) for p in prompts]
    eng.step()
    states = [r.state for r in reqs]
    assert states.count(WAITING) == 2, states
    eng.run()
    assert all(r.state == FINISHED for r in reqs)
    assert eng.peak_blocks_in_use <= eng.kv.capacity_blocks
    assert eng.kv.blocks_in_use == 0
    oracle = [_alone(model_and_params, programs, p, 6) for p in prompts]
    assert [r.out for r in reqs] == oracle


def test_eos_finishes_early_and_frees_blocks(model_and_params, programs):
    # sampled run: varied tokens, so the eos pick is discriminative
    # (greedy on a random-init model collapses to one token)
    prompt = _prompts(seed=13)[1]
    kw = dict(temperature=0.9, top_k=6, seeds=[42])
    full = _alone(model_and_params, programs, prompt, 8, **kw)
    stop_at = next(i for i in range(1, 8) if full[i] not in full[:i])
    eos = full[stop_at]
    eng = _engine(model_and_params, programs)
    r = eng.submit(prompt, 8, temperature=0.9, top_k=6, seed=42,
                   eos_token=eos)
    eng.run()
    assert r.out == full[:stop_at + 1]   # eos included, then stop
    assert len(r.out) < 8
    assert r.state == FINISHED
    assert eng.kv.blocks_in_use == 0


def test_prefill_final_chunk_past_wpe_table_stays_exact():
    """Regression: when the final (padded) prefill chunk runs past the
    wpe table (max_seq_len not a chunk multiple), the VALID rows must
    keep their exact positional embeddings — a dynamic_slice would
    clamp its start backwards and silently shift them."""
    model = GPT(gpt2_config("nano", num_layers=2, num_heads=4, d_model=32,
                            vocab_size=VOCAB, max_seq_len=30))
    params = model.init(jax.random.PRNGKey(2))
    # chunk 8: prompt 27 -> final chunk at pos 24 wants wpe[24:32] but
    # the table has 30 rows
    eng = ServeEngine(model, params, ServeConfig(
        block_size=BS, num_blocks=40, max_batch=2, prefill_chunk=8,
        max_seq_len=30))
    prompt = _prompts(seed=41, lens=(27,))[0]
    got = eng.generate([prompt], 3)[0]
    want = np.asarray(generate(
        model, params, np.asarray([prompt], np.int32), 3,
        cache_len=eng.kv.table_width * BS))[0].tolist()
    assert got == want


def test_idle_engine_does_not_trip_watchdog(model_and_params, programs):
    """Regression: a ServeWorker with no traffic beats the watchdog
    from its idle loop — quiet periods are not hangs and must not
    shed/escalate."""
    import tempfile
    import time

    from deepspeed_tpu.runtime.resilience import StepWatchdog
    from deepspeed_tpu.serving import ServeWorker

    eng = _engine(model_and_params, programs)
    with tempfile.TemporaryDirectory() as d:
        wd = StepWatchdog(deadline_s=0.2, snapshot_dir=d, poll_s=0.05,
                          on_trip=lambda t: eng.request_shed(t["reason"]))
        eng.attach_watchdog(wd)
        w = ServeWorker(eng)
        w.start()
        try:
            r = eng.submit(_prompts()[0], 4)
            t0 = time.monotonic()
            while not r.done and time.monotonic() - t0 < 30:
                time.sleep(0.01)
            # idle for several deadlines AFTER the traffic drains
            time.sleep(0.6)
            assert wd.trips == 0, "idle period tripped the watchdog"
            # and the watchdog still works for real wedges afterwards
            assert r.state == FINISHED
        finally:
            w.stop()
            eng.close()
            wd.stop()


def test_corrupt_serving_json_names_the_real_defect(tmp_path):
    from deepspeed_tpu.monitor.report import load_run

    run_dir = tmp_path / "svrun"
    run_dir.mkdir()
    (run_dir / "serving.json").write_text('{"lanes": {"contin')  # torn
    with pytest.raises(ValueError, match="serving.json"):
        load_run(str(run_dir))


def test_chunked_prefill_token_identical_to_one_shot(model_and_params):
    """prefill_chunk 4 vs 32 (whole prompt in one call) — chunking is
    a scheduling choice, never a numerics choice."""
    model, params = model_and_params
    prompt = _prompts(seed=17, lens=(19,))[0]
    outs = {}
    for chunk in (4, 32):
        eng = ServeEngine(model, params, _cfg(prefill_chunk=chunk))
        outs[chunk] = eng.generate([prompt], 6)[0]
    assert outs[4] == outs[32]


def test_static_admission_policy_blocks_until_batch_drains(
        model_and_params, programs):
    prompts = _prompts(seed=19)
    eng = _engine(model_and_params, programs, admission="static")
    r_first = eng.submit(prompts[0], 8)
    eng.step()
    r_late = eng.submit(prompts[1], 4)
    eng.step()
    # static: the late request cannot join the occupied batch
    assert r_late.state == WAITING
    eng.run()
    assert r_first.state == FINISHED and r_late.state == FINISHED
    # outputs are policy-independent (the invariance contract)
    assert r_first.out == _alone(model_and_params, programs, prompts[0], 8)
    assert r_late.out == _alone(model_and_params, programs, prompts[1], 4)


# -- the loop, one decode step ahead of what the host has read --------------


@pytest.mark.parametrize("kw", [{}, dict(temperature=0.8, top_k=5)],
                         ids=["greedy", "sampled"])
def test_one_step_ahead_is_token_identical_to_generate_alone(
        model_and_params, programs, kw):
    """Six requests over four slots with answers of different lengths:
    they join while others decode, leave at different steps, and the
    later ones sit in slots and blocks the earlier ones gave back.  Each
    gets, token for token, what `generate()` gives it alone, while nearly
    every decode step was launched before the one before it was read."""
    prompts = _prompts(seed=43, lens=(5, 9, 3, 12, 7, 4))
    news = (9, 3, 12, 2, 6, 1)
    oracle = [_alone(model_and_params, programs, p, n,
                     seeds=[70 + i], **kw)
              for i, (p, n) in enumerate(zip(prompts, news))]
    eng = _engine(model_and_params, programs)
    snap = COUNTERS.snapshot()
    reqs = []
    for i, (p, n) in enumerate(zip(prompts, news)):
        reqs.append(eng.submit(p, n, seed=70 + i, **kw))
        eng.step()
        if i % 2:
            eng.step()
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert [r.out for r in reqs] == oracle
    assert all(r.state == FINISHED for r in reqs)
    assert eng.kv.blocks_in_use == 0 and not eng._unread
    ahead = d["serve.decode_ahead"]
    assert ahead["bytes"] >= ahead["calls"] - 2 > 0, ahead
    # an end by max_new_tokens is decided ahead: nothing is decoded for a
    # request whose last token a launched step already computes
    assert d["serve.decode_steps"]["bytes"] == sum(n - 1 for n in news)
    assert "serve.decode_ahead.dropped" not in d


def test_eos_is_found_one_step_late_and_costs_one_lane_step(
        model_and_params, programs):
    """One slot samples its `eos_token` while two others run: the step
    launched before the host saw it decodes that slot once more.  That
    token is dropped, nothing follows the EOS in `out`, the request's
    blocks are free once the host has read the EOS, and a request
    admitted into them decodes exactly."""
    prompts = _prompts(seed=13)
    kw = dict(temperature=0.9, top_k=6)
    full = _alone(model_and_params, programs, prompts[1], 8, seeds=[42],
                  **kw)
    stop_at = next(i for i in range(1, 7) if full[i] not in full[:i])
    eng = _engine(model_and_params, programs, num_blocks=21)
    snap = COUNTERS.snapshot()
    others = [eng.submit(prompts[0], 14), eng.submit(prompts[3], 14)]
    r = eng.submit(prompts[1], 8, seed=42, eos_token=full[stop_at], **kw)
    mine = None
    while not r.done:
        eng.step()
        mine = set(eng.kv.blocks_of(r.rid)) or mine
    assert r.out == full[:stop_at + 1] and r.state == FINISHED
    assert not eng.kv.blocks_of(r.rid)
    assert not any(o.done for o in others)
    late = eng.submit(prompts[2], 6)
    eng.step()                      # admitted while the others decode
    assert set(eng.kv.blocks_of(late.rid)) & mine
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert d["serve.decode_ahead.dropped"] == {"calls": 1, "bytes": 0}, d
    assert late.out == _alone(model_and_params, programs, prompts[2], 6)
    assert [o.out for o in others] == [
        _alone(model_and_params, programs, p, 14)
        for p in (prompts[0], prompts[3])]
    assert eng.kv.blocks_in_use == 0


def test_slot_state_handed_to_a_launched_step_is_never_rewritten(
        model_and_params, programs):
    """A program reads its arguments when it RUNS, and on the CPU
    `jnp.asarray` may alias a host array: every array of slot state a
    decode step was launched with must still hold, after the run, what
    it held at the launch — whatever joined, left or moved on since.
    And a step that nobody joins or leaves uploads nothing: it is handed
    the arrays the step before was, and that step's own results."""
    eng = _engine(model_and_params, programs)
    decode = eng.programs["decode"]
    launches = []

    def recording(params, caches, *state):
        out = decode(params, caches, *state)
        launches.append((state, [np.array(a) for a in state], out[2]))
        return out

    eng.programs = dict(eng.programs, decode=recording)
    prompts = _prompts(seed=47)
    eng.submit(prompts[0], 12)
    eng.step()
    eng.submit(prompts[1], 3)
    for _ in range(4):
        eng.step()
    eng.submit(prompts[2], 5)
    eng.run()
    assert len(launches) >= 11
    for state, seen, _ in launches:
        for handed, at_launch in zip(state, seen):
            assert np.array_equal(np.asarray(handed), at_launch)
    steady = 0
    for (a, _, ahead), (b, _, _) in zip(launches, launches[1:]):
        if all(x is y for x, y in zip(a[3:], b[3:])):
            # nobody joined or left: tokens and positions are the step
            # before's results, the rest the very arrays it was handed
            assert b[0] is ahead[0] and b[1] is ahead[1]
            steady += 1
    assert steady >= 5


@pytest.mark.parametrize("how", ["shed", "worker_death", "close"])
def test_nothing_in_flight_outlives_its_requests(model_and_params, programs,
                                                 how):
    """A shed, a dead worker and `close()` meet the engine with a decode
    step launched and unread and a request still waiting: afterwards no
    request is non-terminal, no block is booked, nothing is unread."""
    from deepspeed_tpu.serving import ServeWorker

    prompts = _prompts(seed=53, lens=(5, 9, 6))
    eng = _engine(model_and_params, programs, num_blocks=13)
    reqs = [eng.submit(p, 12) for p in prompts]
    for _ in range(3):
        eng.step()
    assert len(eng._unread) == 1 and eng.kv.blocks_in_use > 0
    assert reqs[2].state == WAITING
    if how == "shed":
        eng.request_shed("test wedge")
        eng.step()
        assert [r.state for r in reqs[:2]] == [ERROR, ERROR]
        assert not eng._unread or reqs[2].state != WAITING
        eng.run()
        assert reqs[2].state == FINISHED
        assert reqs[2].out == _alone(model_and_params, programs,
                                     prompts[2], 12)
    elif how == "worker_death":
        real, calls = eng.step, []

        def step_then_die():
            calls.append(1)
            if len(calls) > 2:
                raise RuntimeError("injected engine failure")
            return real()

        eng.step = step_then_die
        w = ServeWorker(eng)
        w.start()
        w.join(timeout=30.0)
        assert not w.is_alive()
        assert all(r.state == ERROR and "injected" in r.error for r in reqs)
        with pytest.raises(RuntimeError, match="injected engine failure"):
            w.stop()
    else:
        eng.close()
        assert all(r.state == ERROR and r.error == "engine closed"
                   for r in reqs)
    assert all(r.done for r in reqs)
    assert eng.kv.blocks_in_use == 0 and not eng._unread
    assert not eng.has_work()


def test_drafting_keeps_the_serial_loop(model_and_params):
    """`draft_len` > 0 reads a step's tokens before it proposes the next
    step's candidates: nothing is ever left unread by `step()`, no step
    counts as launched ahead, and the output is the plain engine's."""
    model, params = model_and_params
    prompts = _prompts(seed=59)
    plain = ServeEngine(model, params, _cfg()).generate(prompts[:2], 10)
    eng = ServeEngine(model, params, _cfg(draft_len=2))
    snap = COUNTERS.snapshot()
    reqs = [eng.submit(p, 10) for p in prompts[:2]]
    while eng.has_work():
        eng.step()
        assert not eng._unread
    d = COUNTERS.delta_since(snap)
    assert [r.out for r in reqs] == plain
    assert "serve.decode_ahead" not in d and d["serve.decode_steps"]["calls"]


# -- the sampling tail: only the work the call's rows ask for ---------------


def _sorted_rule(logits, temperature, top_k, key):
    """The rule the programs sampled by until they stopped sorting: one
    row, a full descending sort for the k-th largest value.  Kept here
    as the oracle: (token, filtered logits)."""
    greedy = jnp.argmax(logits, axis=-1)
    v = logits.shape[-1]
    t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits.astype(jnp.float32) / t
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    kth = sorted_desc[jnp.clip(top_k, 1, v) - 1]
    filtered = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    sampled = jax.random.categorical(key, filtered, axis=-1)
    return (jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32),
            filtered)


def _sorted_rows(logits, temperatures, top_ks, live, keys):
    """`sample_rows` as it was: every row sorts, whatever it asks for."""
    return jax.vmap(_sorted_rule)(logits, temperatures, top_ks, keys())[0]


def _mixed_call(vocab, dtype, seed):
    """A call's rows, one of each kind: greedy, and sampled with
    `top_k` 0 / 1 / 40 / V / above V — over logits on a grid of halves,
    so that the k-th value is shared by several columns, the top few
    columns exactly equal, a run of -inf in every row and one row with
    fewer finite values than its `top_k`."""
    rs = np.random.RandomState(seed)
    top_ks = np.array([0, 0, 1, 40, vocab, vocab + 7, 40, 3, 5], np.int32)
    temps = np.array([0, .7, .7, 1.3, .9, .7, 0, 2.5, .7], np.float32)
    n = len(top_ks)
    logits = np.round(rs.randn(n, vocab) * 3) / 2
    logits[:, rs.permutation(vocab)[:4]] = 6.0          # tied at the top
    logits[:, rs.permutation(vocab)[:vocab // 5]] = -np.inf
    logits[-1, rs.permutation(vocab)[:vocab - 3]] = -np.inf
    logits[1] += rs.randn(vocab) * 1e-3                 # and one row untied
    keys = jax.vmap(jax.random.PRNGKey)(
        jnp.asarray(rs.randint(0, 2 ** 31 - 1, n), jnp.uint32))
    return (jnp.asarray(logits, dtype), jnp.asarray(temps),
            jnp.asarray(top_ks), keys)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [64, 200, 1031])
def test_sampling_tail_draws_what_the_sorted_rule_draws(vocab, dtype, seed):
    """Every row of a mixed call gets the sorted rule's token, and a
    row that sets `top_k` the sorted rule's filtered logits bit for bit
    — ties at the threshold, -inf, `top_k` at and above V, a vocabulary
    that is no multiple of 128."""
    logits, temps, top_ks, keys = _mixed_call(vocab, dtype, seed)
    live = jnp.ones(temps.shape, bool)
    want_tok, want_filtered = jax.vmap(_sorted_rule)(logits, temps, top_ks,
                                                     keys)
    got = jax.jit(sample_rows, static_argnums=4)(
        logits, temps, top_ks, live, lambda: keys)
    assert np.array_equal(np.asarray(got), np.asarray(want_tok))
    # the draws must actually vary, or equal tokens would say nothing
    assert len(set(np.asarray(got)[np.asarray(temps) > 0].tolist())) > 1
    t = jnp.where(temps > 0, temps, 1.0)
    scaled = logits.astype(jnp.float32) / t[:, None]
    got_filtered = jax.jit(top_k_filter)(scaled, top_ks)
    assert np.array_equal(np.asarray(got_filtered).view(np.uint32),
                          np.asarray(want_filtered).view(np.uint32))
    cut = np.isneginf(np.asarray(got_filtered)) & ~np.isneginf(
        np.asarray(scaled))
    assert cut[3].any() and not cut[[0, 1, 4, 5]].any()


def test_kth_largest_is_the_sorts_own_entry():
    """The selection against the sort it replaces, value by value: every
    k of a row that holds both zeros, both infinities and repeats (the
    two zeros compare equal, and a backend may sort them either way)."""
    row = np.array([0.0, -0.0, np.inf, -np.inf, 1e-30, -1e-30, 1.5, 1.5,
                    -1.5, 3e38, -3e38, 1.0000001, 1.0, -np.inf, 0.0],
                   np.float32)
    v = len(row)
    x = jnp.asarray(np.tile(row, (v, 1)))
    got = jax.jit(kth_largest)(x, jnp.arange(1, v + 1, dtype=jnp.int32))
    want = jnp.sort(jnp.asarray(row))[::-1]
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _tail_taken(temps, top_ks, live, logits=None):
    """(tokens, whether the sampling branch RAN) for one call."""
    n = len(temps)
    if logits is None:
        logits = jnp.asarray(np.random.RandomState(5).randn(n, VOCAB),
                             jnp.float32)
    ran = []

    def keys():
        jax.debug.callback(lambda: ran.append(1))
        return jax.vmap(jax.random.PRNGKey)(jnp.arange(n, dtype=jnp.uint32))

    toks = jax.jit(sample_rows, static_argnums=4)(
        logits, jnp.asarray(temps, jnp.float32),
        jnp.asarray(top_ks, jnp.int32), jnp.asarray(live, bool), keys)
    jax.effects_barrier()
    return np.asarray(toks), bool(ran), logits


@pytest.mark.parametrize("temps,live,samples", [
    ((0, 0, 0), (1, 1, 1), False),
    ((0, .8, 0), (1, 0, 1), False),     # a freed slot's stale temperature
    ((.8, .8, .8), (0, 0, 0), False),
    ((0, .8, 0), (1, 1, 1), True),
    ((0, .8, .8), (0, 0, 1), True),
], ids=["greedy", "stale", "all-stale", "one-samples", "last-samples"])
def test_no_live_sampling_row_no_sampling_tail(temps, live, samples):
    """The sampling branch runs iff a LIVE row has `temperature` > 0; a
    call that stays on the greedy tail returns every row's argmax."""
    toks, ran, logits = _tail_taken(temps, (0, 4, 0), live)
    assert ran == samples
    if not samples:
        assert toks.tolist() == np.argmax(np.asarray(logits), -1).tolist()


@pytest.mark.parametrize("neighbours", [
    dict(temps=(0, 0, 0), top_ks=(0, 0, 0)),
    dict(temps=(0, .8, 1.2), top_ks=(0, 0, 0)),
    dict(temps=(0, .8, 1.2), top_ks=(0, 5, 0)),
    dict(temps=(0, .8, 1.2), top_ks=(3, 5, 70)),
], ids=["greedy", "sampling", "one-top-k", "all-top-k"])
def test_a_rows_token_does_not_depend_on_its_neighbours_tail(neighbours):
    """Row 0 (greedy) and row 3 (sampled, no `top_k`) beside neighbours
    that take the call through each of the three tails."""
    temps = neighbours["temps"] + (0.9,)
    top_ks = neighbours["top_ks"] + (0,)
    toks, _, logits = _tail_taken(temps, top_ks, (1, 1, 1, 1))
    assert toks[0] == int(jnp.argmax(logits[0]))
    # row 3 alone, with the key `_tail_taken` gives its index
    want = _sorted_rule(logits[3], jnp.float32(0.9), jnp.int32(0),
                        jax.random.PRNGKey(jnp.uint32(3)))[0]
    assert toks[3] == int(want)


@pytest.mark.parametrize("top_k", [0, 5, VOCAB + 3])
@pytest.mark.parametrize("draft_len", [0, 2], ids=["decode", "verify"])
def test_programs_draw_the_sorted_rules_tokens(model_and_params, monkeypatch,
                                               draft_len, top_k):
    """`prefill`, `decode` and `verify` built around the sorted rule and
    built as they are give the same answers at `temperature` > 0, with a
    greedy request decoding beside the sampled ones."""
    model, params = model_and_params
    prompts = _prompts(seed=61)

    def answers():
        eng = ServeEngine(model, params, _cfg(draft_len=draft_len))
        reqs = [eng.submit(p, 9, seed=300 + i,
                           temperature=0.0 if i == 2 else 0.6 + i / 4,
                           top_k=top_k if i % 2 else 0)
                for i, p in enumerate(prompts)]
        eng.run()
        return [r.out for r in reqs]

    monkeypatch.setattr(programs_mod, "sample_rows", _sorted_rows)
    want = answers()
    monkeypatch.undo()
    got = answers()
    assert got == want
    assert any(len(set(o)) > 2 for o in got)


def _outside_conds(jaxpr):
    """Names of the primitives a program runs whatever its `cond`s
    decide: its own and its nested calls', not the branches'."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _outside_conds(sub)
    return names


def test_decode_sorts_nothing_and_draws_only_inside_a_branch(
        model_and_params):
    """The lowered `decode` holds no sort at all, and what the sampling
    tail costs — keys, noise bits — stands inside a conditional."""
    eng = _engine(model_and_params)
    seen = {}

    def capture(*args, _fn=eng.programs["decode"]):
        if not seen:
            seen["text"] = _fn.lower(*args).as_text()
            seen["jaxpr"] = jax.make_jaxpr(_fn)(*args).jaxpr
        return _fn(*args)

    eng.programs["decode"] = capture
    eng.generate(_prompts(seed=83, lens=(5,)), 3)
    assert "stablehlo.sort" not in seen["text"]
    assert "top_k" not in seen["text"]
    assert "stablehlo.case" in seen["text"] or "stablehlo.if" in seen["text"]
    always = set(_outside_conds(seen["jaxpr"]))
    assert "cond" in always and "argmax" in always
    assert not always & {"sort", "top_k", "random_bits", "threefry2x32",
                         "random_wrap", "random_fold_in", "random_seed"}


def test_greedy_steps_counter_follows_the_live_rows(model_and_params,
                                                    programs):
    """`serve.sample.greedy_steps`: calls = decode steps launched, bytes
    = those in which no live slot samples — every step of a greedy run,
    none while a sampled request is seated, and every step again once it
    has left its slot (and its temperature) behind."""
    prompts = _prompts(seed=89)
    eng = _engine(model_and_params, programs)
    snap = COUNTERS.snapshot()
    eng.generate(prompts[:2], 5)
    d = COUNTERS.delta_since(snap)
    assert d["serve.sample.greedy_steps"] == {
        "calls": d["serve.decode_ahead"]["calls"],
        "bytes": d["serve.decode_ahead"]["calls"]}, d

    snap = COUNTERS.snapshot()
    greedy = eng.submit(prompts[0], 12)
    sampled = eng.submit(prompts[1], 4, temperature=0.8, top_k=5, seed=3)
    eng.step()
    slot = sampled.slot
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert (len(greedy.out), len(sampled.out)) == (12, 4)
    # 11 decode steps; the sampled request rides the first 3 of them and
    # its slot keeps temperature 0.8, inactive, through the other 8
    assert eng._slots.host["temperatures"][slot] > 0
    assert not eng._slots.host["active"][slot]
    assert d["serve.sample.greedy_steps"] == {"calls": 11, "bytes": 8}, d
    assert greedy.out == _alone(model_and_params, programs, prompts[0], 12)


# -- counters ---------------------------------------------------------------


def test_serving_counters_pinned_exactly(model_and_params, programs):
    prompt = _prompts(seed=23, lens=(5,))[0]
    eng = _engine(model_and_params, programs)
    snap = COUNTERS.snapshot()
    r = eng.submit(prompt, 3)
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert r.out and len(r.out) == 3
    # prompt 5 -> one chunk of 5 valid tokens
    assert d["serve.prefill_chunks"] == {"calls": 1, "bytes": 5}, d
    # token 1 from prefill (same engine step dispatches the first
    # decode), tokens 2..3 from two decode steps of one active slot
    assert d["serve.decode_steps"] == {"calls": 2, "bytes": 2}, d
    assert d["serve.tokens"]["calls"] == 3, d
    assert d["serve.requests"] == {"calls": 1, "bytes": 3}, d
    assert d["serve.ttft_ms"]["calls"] == 1, d
    assert d["serve.ttft_ms"]["bytes"] > 0, d
    # ceil((5 + 3) / 4) = 2 blocks, occupancy sampled per engine step:
    # step 1 = prefill + first decode (2 in use), step 2 = final
    # decode, which finishes + frees before the sample -> [2, 0]
    assert d["kv.blocks_in_use"] == {"calls": 2, "bytes": 2}, d
    assert "kv.evictions" not in d and "serve.shed" not in d
    # the first decode step starts the stretch, the second was launched
    # while the first was unread; nothing ran for a request already ended
    assert d["serve.decode_ahead"] == {"calls": 2, "bytes": 1}, d
    assert "serve.decode_ahead.dropped" not in d

    # a fixed script of arrivals: r1 joins at r0's third decode step
    # and leaves one step later; four steps, all but the first ahead
    snap = COUNTERS.snapshot()
    r0 = eng.submit(prompt, 5)
    eng.step()
    eng.step()
    r1 = eng.submit(prompt[:3], 2)
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert (len(r0.out), len(r1.out)) == (5, 2)
    assert d["serve.decode_ahead"] == {"calls": 4, "bytes": 3}, d
    assert d["serve.decode_steps"] == {"calls": 4, "bytes": 4 + 1}, d
    assert "serve.decode_ahead.dropped" not in d


# -- phases: what the engine's thread says it is doing ----------------------

LEAVES = ("serve.idle", "serve.admit", "serve.prefill.launch",
          "serve.draft", "serve.decode.launch", "serve.read",
          "serve.bookkeep")
# one iteration of step(): the loop that runs ahead, then the serial one
ITERATION = {
    False: r"admit (prefill\.launch )?(decode\.launch )?(read bookkeep )*",
    True: r"admit (prefill\.launch (read bookkeep )?)?"
          r"(draft decode\.launch read bookkeep )?"}


def _recorded(tmp_path, **kw):
    from deepspeed_tpu.monitor.tracing import TraceRecorder

    return TraceRecorder(str(tmp_path), buffer_events=1 << 16,
                         flush_interval_s=10, **kw)


def _check_phases(events, serial):
    """Leaf phases never overlap and follow step()'s order; the uploads
    lie inside the launch that makes them."""
    import re

    spans = [e for e in events if e["name"].startswith("serve.")
             and e["ph"] == "X"]
    leaves = sorted((e for e in spans if e["name"] in LEAVES),
                    key=lambda e: e["ts"])
    assert {e["name"] for e in spans} <= set(LEAVES) | {"serve.decode.upload"}
    for a, b in zip(leaves, leaves[1:]):
        assert a["ts"] + a["dur"] <= b["ts"], (a, b)
    order = "".join(e["name"][len("serve."):] + " " for e in leaves)
    assert re.fullmatch(f"((idle )|({ITERATION[serial]}))*", order), order
    launches = [e for e in leaves if e["name"] == "serve.decode.launch"]
    for up in (e for e in spans if e["name"] == "serve.decode.upload"):
        assert any(la["ts"] <= up["ts"] and up["ts"] + up["dur"]
                   <= la["ts"] + la["dur"] for la in launches), up
    return leaves


@pytest.mark.parametrize("draft_len", [0, 2], ids=["ahead", "serial"])
def test_phases_of_an_iteration_are_disjoint_and_in_order(
        model_and_params, programs, tmp_path, draft_len):
    """With a recorder attached every iteration of `step()` lands as
    leaf phases that do not overlap and come in the loop's order, under
    a worker with requests joining and leaving; `serve.idle` begins only
    once nothing is submitted and nothing is unread."""
    import time

    from deepspeed_tpu.serving import ServeWorker

    eng = _engine(model_and_params, None if draft_len else programs,
                  draft_len=draft_len)
    rec = _recorded(tmp_path)
    eng.attach_tracing(tracer=rec)
    prompts = _prompts(seed=71, lens=(5, 9, 3, 12, 7))
    reqs = [eng.submit(prompts[0], 24)]     # keeps the engine busy
    worker = ServeWorker(eng)
    worker.start()
    try:
        for p, n in zip(prompts[1:], (3, 6, 2, 5)):
            while len(reqs[0].out) < 2 * len(reqs):
                time.sleep(0.001)
            reqs.append(eng.submit(p, n))   # joins; the short ones leave
        deadline = time.time() + 60
        while eng.has_work() and time.time() < deadline:
            time.sleep(0.001)
        time.sleep(0.02)                    # a few idle waits
    finally:
        worker.stop()
    assert all(r.state == FINISHED for r in reqs)
    events = rec.last_events()
    rec.close()
    leaves = _check_phases(events, serial=bool(draft_len))
    seen = {e["name"] for e in leaves}
    assert seen == set(LEAVES) - ({"serve.draft"} if not draft_len else set())
    # work was there from before the worker started until the last
    # request finished: no idle phase begins before that
    done = max(e["ts"] for e in events if e["name"] == "finish")
    idle = [e for e in leaves if e["name"] == "serve.idle"]
    assert idle and all(e["ts"] >= done for e in idle)


def test_decode_step_rids_reproduce_token_times_gaps(model_and_params,
                                                     programs, tmp_path):
    """`first_token` and `decode_step` carry who was stamped and when,
    on the recorder's clock: with the engine and the recorder on one
    clock the gaps between a request's stamps are the gaps of its
    `token_times`, exactly — an EOS found one step late included (its
    dropped lane is in no `rids`)."""
    ticks = iter(range(1, 1 << 20))
    clock = lambda: float(next(ticks))      # whole seconds: exact in us
    model, params = model_and_params
    prompts = _prompts(seed=73, lens=(5, 9, 3, 12))
    kw = dict(temperature=0.9, top_k=6, seed=42)
    full = _alone(model_and_params, programs, prompts[3], 8, seeds=[42],
                  temperature=0.9, top_k=6)
    stop_at = next(i for i in range(1, 7) if full[i] not in full[:i])
    eng = ServeEngine(model, params, _cfg(), programs=programs, clock=clock)
    rec = _recorded(tmp_path, clock=clock)
    eng.attach_tracing(tracer=rec)
    snap = COUNTERS.snapshot()
    reqs = []
    for p, n in zip(prompts[:3], (9, 3, 12)):
        reqs.append(eng.submit(p, n))
        eng.step()
    reqs.append(eng.submit(prompts[3], 8, eos_token=full[stop_at], **kw))
    eng.run()
    events = rec.last_events()
    rec.close()
    stamps = {r.rid: [] for r in reqs}
    for e in events:
        if e["name"] == "first_token":
            stamps[e["args"]["rid"]].append(e["args"]["stamp_us"])
        elif e["name"] == "decode_step":
            assert len(e["args"]["rids"]) <= e["args"]["batch"]
            assert e["ts"] <= e["args"]["stamp_us"] <= e["ts"] + e["dur"]
            for rid in e["args"]["rids"]:
                stamps[rid].append(e["args"]["stamp_us"])
    assert reqs[3].out == full[:stop_at + 1]    # ended by its EOS
    assert COUNTERS.delta_since(snap)["serve.decode_ahead.dropped"][
        "calls"] == 1
    for r in reqs:
        assert len(stamps[r.rid]) == len(r.token_times) == len(r.out)
        assert [(b - a) / 1e6 for a, b in zip(stamps[r.rid],
                                              stamps[r.rid][1:])] == \
            [b - a for a, b in zip(r.token_times, r.token_times[1:])]


def test_programs_lower_the_same_with_and_without_a_recorder(
        model_and_params, tmp_path):
    """Phases are host annotations: `prefill`, `decode` and `seat` lower
    to the same text whether or not a recorder is attached."""

    def lowered(recorder):
        eng = _engine(model_and_params)     # its own programs
        if recorder is not None:
            eng.attach_tracing(tracer=recorder)
        texts = {}
        for name in ("prefill", "decode", "seat"):
            def capture(*args, _fn=eng.programs[name], _name=name):
                texts.setdefault(_name, _fn.lower(*args).as_text())
                return _fn(*args)
            eng.programs[name] = capture
        eng.generate(_prompts(seed=79, lens=(5, 9)), 4)
        return texts

    rec = _recorded(tmp_path)
    with_rec, without = lowered(rec), lowered(None)
    rec.close()
    assert set(with_rec) == {"prefill", "decode", "seat"}
    assert with_rec == without


# -- chaos: wedged decode -> watchdog trip -> shed --------------------------


def test_wedged_decode_sheds_requests_not_the_fleet():
    """The chaos lane (satellite: serve_bench + in-test): a decode-step
    hang trips the StepWatchdog, the wedged batch is shed with an
    error, waiting requests complete with oracle-identical output."""
    import serve_bench

    result = serve_bench.run_dry_chaos(record=False)
    assert result["shed"] == 2
    assert result["watchdog_trips"] == 1
    assert result["survivors_ok"]


def test_shed_requests_report_error_and_evictions(model_and_params,
                                                  programs):
    """request_shed() directly (no watchdog): victims get state
    'error' + the reason, their blocks count as kv.evictions."""
    prompts = _prompts(seed=29)
    eng = _engine(model_and_params, programs)
    snap = COUNTERS.snapshot()
    r0 = eng.submit(prompts[0], 8)
    r1 = eng.submit(prompts[1], 8)
    for _ in range(3):
        eng.step()
    held = eng.kv.blocks_in_use
    assert held > 0
    eng.request_shed("test wedge")
    r2 = eng.submit(prompts[2], 4)
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert r0.state == ERROR and "test wedge" in r0.error
    assert r1.state == ERROR
    assert r2.state == FINISHED
    assert r2.out == _alone(model_and_params, programs, prompts[2], 4)
    assert d["serve.shed"]["calls"] == 2
    assert d["kv.evictions"]["calls"] == held
    assert eng.kv.blocks_in_use == 0


def test_worker_death_fails_requests_loudly(model_and_params, programs):
    """A ServeWorker that dies marks every non-terminal request
    'error' (never a silent hang) and re-raises on stop()."""
    from deepspeed_tpu.serving import ServeWorker

    eng = _engine(model_and_params, programs)
    orig = eng.step

    def boom():
        raise RuntimeError("injected engine failure")

    eng.step = boom
    w = ServeWorker(eng)
    w.start()
    r = eng.submit(_prompts()[0], 4)
    w.join(timeout=10.0)
    assert not w.is_alive()
    assert r.state == ERROR and "injected engine failure" in r.error
    with pytest.raises(RuntimeError, match="injected engine failure"):
        w.stop()
    eng.step = orig


# -- quantized weights / mesh sharding --------------------------------------


def test_qwz_weights_invariance_and_memory_shape(model_and_params):
    """int8 qwZ weights: the invariance contract holds unchanged, and
    matmul leaves really are stored quantized (uint8/int8 + fp16
    scales)."""
    from deepspeed_tpu.serving.programs import QuantLeaf

    model, params = model_and_params
    cfg = _cfg(quantized_weights="int8")
    eng = ServeEngine(model, params, cfg)
    qleaves = [l for l in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QuantLeaf))
        if isinstance(l, QuantLeaf)]
    assert qleaves, "no quantized leaves found"
    assert all(l.payload.dtype == jnp.int8 for l in qleaves)
    assert all(l.scales.dtype == jnp.float16 for l in qleaves)

    prompts = _prompts(seed=31)
    batched = eng.generate(prompts[:3], 6, temperature=0.7, top_k=8,
                           seeds=[1, 2, 3])
    alone = ServeEngine(model, params, cfg, programs=eng.programs)
    assert alone.generate([prompts[1]], 6, temperature=0.7, top_k=8,
                          seeds=[2])[0] == batched[1]


def test_mesh_sharded_kv_cache_invariance(model_and_params):
    """TP=2 mesh: the KV cache shards its head dimension over `model`,
    and batching invariance still holds exactly (same program, same
    shardings for the alone and batched runs)."""
    from deepspeed_tpu.comm.mesh import make_mesh

    model, params = model_and_params
    info = make_mesh(data=1, model=2, devices=jax.devices()[:2])
    eng = ServeEngine(model, params, _cfg(), mesh_info=info)
    assert eng.kv._sharding is not None, "cache should shard over model"
    prompts = _prompts(seed=37)
    batched = eng.generate(prompts[:3], 6, temperature=0.7, top_k=8,
                           seeds=[1, 2, 3])
    eng2 = ServeEngine(model, params, _cfg(), mesh_info=info,
                       programs=eng.programs)
    assert eng2.generate([prompts[0]], 6, temperature=0.7, top_k=8,
                         seeds=[1])[0] == batched[0]


# -- validation -------------------------------------------------------------


def test_config_and_submit_validation(model_and_params, programs):
    model, params = model_and_params
    with pytest.raises(ValueError, match="admission"):
        ServeConfig(admission="greedy")
    with pytest.raises(ValueError, match="num_blocks"):
        ServeConfig(num_blocks=1)
    with pytest.raises(ValueError, match="quantized_weights"):
        ServeConfig(quantized_weights="fp8")
    with pytest.raises(ValueError, match="max_seq_len"):
        ServeEngine(model, params, _cfg(max_seq_len=MAX_SEQ * 2))

    eng = _engine(model_and_params, programs)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(list(range(60)), 10)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], 0)
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2], 4, temperature=-1.0)
    # a tiny pool can never serve a request wider than its free list
    small = _engine(model_and_params, num_blocks=3, max_seq_len=32)
    with pytest.raises(ValueError, match="KV blocks"):
        small.submit(list(range(10)), 10)


def test_prebuilt_program_schedule_mismatch_is_loud(model_and_params,
                                                    programs):
    model, params = model_and_params
    with pytest.raises(ValueError, match="prebuilt programs"):
        ServeEngine(model, params, _cfg(max_batch=2), programs=programs)


def test_moe_and_pipeline_configs_rejected():
    model = GPT(gpt2_config("nano", vocab_size=VOCAB, num_experts=4,
                            moe_top_k=2))
    sched = ServeSchedule(max_batch=2, prefill_chunk=8, block_size=BS,
                          num_blocks=8, table_width=WIDTH)
    with pytest.raises(NotImplementedError, match="dense GPT"):
        ServeProgramBuilder(model, sched)


# -- the bench lane ---------------------------------------------------------


def test_serve_bench_dry_run():
    """tools/serve_bench.py --dry-run (tier-1 so the lane cannot rot):
    both admission lanes complete every request and agree on token
    totals (the invariance contract seen from the bench)."""
    import serve_bench

    result = serve_bench.run_dry(record=False)
    for lane in result["lanes"].values():
        assert lane["completed"] == lane["requests"]
        assert lane["errored"] == 0
    assert result["lanes"]["continuous"]["tokens"] == \
        result["lanes"]["static"]["tokens"]
