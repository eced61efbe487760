"""EvaByte through the serving engine, against its plain reference
(benchmarks/reference/evabyte.py), at a small size on the CPU: 2 layers,
64 wide, 4 heads of 16, window 32, chunk 4, vocabulary 320, seeded
weights.

(a) `EvaByte.apply` equals the reference; (b) the engine — prefill in
chunks, then decode through the cache, windows closing in prefill, at the
prefill/decode boundary and in mid-decode, slots joining and leaving —
gives the reference's LOGITS at every generated position; (c) with the
window at least the sequence, or the chunk 1, EVA is plain causal softmax
attention, in the reference and in the system; (d) the cache's
invariants: bounded exact blocks, closed windows' blocks back on the free
list, summary rows counted, the free list whole afterwards, admission by
bounded footprint; (e) what the engine refuses for this family, by name.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import EvaByte, EvaByteConfig
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (PagedKVCache, ServeConfig, ServeEngine,
                                   ServeProgramBuilder, ServeSchedule)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.reference import evabyte as ref  # noqa: E402
from toy_plans import toy_plan  # noqa: E402

W, C, VOCAB = 32, 4, 320
KW = dict(heads=4, eps=1e-5, theta=1e5, window=W, chunk=C, vocab=VOCAB)


def _config(**kw):
    base = dict(max_seq_len=256, num_layers=2, num_heads=4, d_model=64,
                d_ff=176, window_size=W, chunk_size=C, attn_out_std=0.3)
    base.update(kw)
    return EvaByteConfig(**base)


def _serve(**kw):
    base = dict(block_size=C, num_blocks=96, max_batch=3, prefill_chunk=8,
                max_seq_len=256, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def model_and_params():
    model = EvaByte(_config())
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


class Probe:
    """An engine whose every sampled token's logits are kept: the
    prefill program returns its last row's, and the decode step's are
    read by jitting the builder's own `step_logits` on the inputs the
    engine is about to hand to `decode`.  `logits[rid][t]` is what
    `out[t]` was drawn from."""

    def __init__(self, model, params, config):
        self.engine = eng = ServeEngine(model, params, config)
        self.logits = {}
        builder = ServeProgramBuilder(model, eng.programs["schedule"])
        step = jax.jit(builder.step_logits)
        prefill, decode = eng.programs["prefill"], eng.programs["decode"]

        def probed_prefill(*args):
            tok, lg, caches = prefill(*args)
            self._last_prefill = np.asarray(lg)
            return tok, lg, caches

        def probed_decode(params, caches, tokens, positions, active, tables,
                          *sampling):
            lg = np.asarray(step(params, caches, tokens, positions, active,
                                 tables)[0])
            live = np.asarray(active)
            for req in eng.scheduler.running():
                # a request whose prefill ended in this very step
                # decodes in it too: its first logits are the prefill's;
                # one whose last token the step before computes is still
                # running, unread, and is not decoded for
                if live[req.slot]:
                    self.logits.setdefault(req.rid, [self._last_prefill]) \
                        .append(lg[req.slot])
            return decode(params, caches, tokens, positions, active, tables,
                          *sampling)

        eng.programs = dict(eng.programs, prefill=probed_prefill,
                            decode=probed_decode)

    def step(self):
        before = {r.rid: len(r.out) for r in self.engine.scheduler.requests}
        did = self.engine.step()
        for r in self.engine.scheduler.requests:
            if before[r.rid] == 0 and len(r.out) >= 1 \
                    and r.rid not in self.logits:
                self.logits[r.rid] = [self._last_prefill]
        return did

    def run(self):
        while self.engine.has_work():
            self.step()


def _attention(impl):
    """The scope in which an engine's programs are traced with the
    `eva_attention` op forced to the jnp oracle or to the Pallas kernel
    (under the interpreter here)."""
    from deepspeed_tpu.kernels import kernel_config

    return kernel_config(ops={"eva_attention": impl}, interpret=True)


IMPLS = pytest.mark.parametrize("impl", ["jnp", "pallas"])


def _reference_rows(params, req, kw=KW):
    """The reference's logits at the positions that chose req.out."""
    lg = ref.logits(params, jnp.asarray([req.prompt + req.out]), **kw)[0]
    first = len(req.prompt) - 1
    return np.asarray(lg[first:first + len(req.out)])


# -- (a) the uncached forward against the reference ---------------------------


@pytest.mark.parametrize("seq", [3, 32, 90])
def test_apply_matches_reference(model_and_params, seq):
    model, params = model_and_params
    toks = jnp.asarray([_prompt(seq, 1), _prompt(seq, 2)])
    got = model.apply(params, toks)
    assert got.shape == (2, seq, 8 * VOCAB) and got.dtype == jnp.float32
    want = ref.logits(params, toks, **KW)
    np.testing.assert_allclose(got[..., :VOCAB], want, atol=1e-5, rtol=0)


def test_reference_imports_nothing_of_the_system():
    import re

    with open(ref.__file__) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(deepspeed_tpu|benchmarks)",
                         src, re.M)
    assert 'HIGHEST = "highest"' in src and "float32" in src


# -- (c) the two exact properties ---------------------------------------------


def _plain_causal(q, k, v):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("window,chunk", [(128, 4), (32, 1), (4, 1)],
                         ids=["window>=S", "chunk=1", "chunk=1,window=4"])
@pytest.mark.parametrize("where", ["reference", "system"])
def test_eva_is_plain_attention_when_nothing_is_summarised(where, window,
                                                           chunk):
    from deepspeed_tpu.models.evabyte import eva_attention_full

    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k, v = (jax.random.normal(kk, (2, 90, 4, 16)) for kk in keys[:3])
    mu, phi = (jax.random.normal(kk, (4, 16)) for kk in keys[3:])
    fn = ref.attention if where == "reference" else eva_attention_full
    with jax.default_matmul_precision("highest"):
        got = fn(q, k, v, mu, phi, window=window, chunk=chunk)
        np.testing.assert_allclose(got, _plain_causal(q, k, v), atol=2e-6,
                                   rtol=0)
        # and it is NOT plain attention once chunks are summarised
        eva = fn(q, k, v, mu, phi, window=W, chunk=C)
        assert float(jnp.abs(eva - _plain_causal(q, k, v)).max()) > 0.05


@pytest.mark.parametrize("kw", [dict(window_size=128), dict(chunk_size=1)],
                         ids=["window>=S", "chunk=1"])
def test_engine_is_plain_attention_when_nothing_is_summarised(kw):
    """The system end to end: served through the cache with the window at
    least the sequence, or one-token chunks, the logits are those of the
    reference given a window that never closes."""
    model = EvaByte(_config(**kw))
    params = jax.jit(model.init)(jax.random.PRNGKey(5))
    probe = Probe(model, params, _serve(
        block_size=model.config.chunk_size, num_blocks=160))
    req = probe.engine.submit(_prompt(50, 4), 40)
    probe.run()
    plain = dict(KW, window=4096)
    np.testing.assert_allclose(np.stack(probe.logits[req.rid])[:, :VOCAB],
                               _reference_rows(params, req, plain),
                               atol=1e-4, rtol=0)


# -- (b) the engine against the reference, logits at every position ------------


@IMPLS
def test_engine_logits_match_reference_across_window_closes(
        model_and_params, impl):
    """Six requests over three slots, joining and leaving mid-flight;
    between them windows close inside a prefill run (70: at 32 and 64),
    exactly at the prefill/decode boundary (64) and in mid-decode (33 +
    40 reaches 64; 5 + 70 reaches 32 and 64).  Every sequence but the
    shortest spans at least 2.5 windows.  The attention core is the
    oracle's gather of the whole table, or the kernel's walk of the
    live blocks: the same logits within the same tolerance, with slots
    idle, blocks returned at a close and taken again by a neighbour."""
    model, params = model_and_params
    probe = Probe(model, params, _serve())
    eng = probe.engine
    lens = [(70, 30), (64, 24), (33, 47), (5, 75), (96, 9), (31, 2)]
    reqs = [eng.submit(_prompt(n, i), new) for i, (n, new) in enumerate(lens)]
    before = COUNTERS.snapshot()
    with _attention(impl):
        probe.run()
    traced = COUNTERS.delta_since(before)
    assert ("kernel.dispatches" in traced) == (impl == "pallas")
    assert all(r.state == "finished" for r in reqs)
    assert eng.peak_resident == 3
    for r in reqs:
        got = np.stack(probe.logits[r.rid])
        assert got.shape == (len(r.out), 8 * VOCAB)
        want = _reference_rows(params, r)
        np.testing.assert_allclose(got[:, :VOCAB], want, atol=1e-4, rtol=0)
        assert r.out == list(np.argmax(got[:, :VOCAB], axis=-1))


def test_a_window_closing_in_prefill_at_the_boundary_and_in_decode_agree(
        model_and_params):
    """The same bytes three ways: decoded from a short prompt (both
    closes happen in mid-decode), from a prompt of exactly two windows
    (the second close falls on the prefill/decode boundary) and from a
    prompt that passes both closes inside its prefill run.  The logits
    at the positions all three generate are the same."""
    model, params = model_and_params
    runs = []
    first = Probe(model, params, _serve())
    a = first.engine.submit(_prompt(20, 9), 80)
    first.run()
    seq = a.prompt + a.out
    runs.append(np.stack(first.logits[a.rid])[70 - 20:])
    for cut in (64, 70):
        probe = Probe(model, params, _serve())
        r = probe.engine.submit(seq[:cut], 100 - cut)
        probe.run()
        assert r.out == seq[cut:]
        runs.append(np.stack(probe.logits[r.rid])[70 - cut:])
    for other in runs[1:]:
        np.testing.assert_allclose(other, runs[0], atol=1e-4, rtol=0)


@IMPLS
def test_engine_bf16_stays_within_its_stated_tolerance(impl):
    """bf16 weights and cache against the float32 reference on the same
    (bf16-rounded) weights.  Tolerance 0.005 on logits whose standard
    deviation is 0.10 at this size: every matmul rounds its inputs to 8
    bits (relative 2^-9) and K/V and summary rows are stored rounded,
    which over two layers moves a logit by 0.0014 at worst over these
    100 positions (measured); the bound is 3.5 times that.  Dropping
    the remote term moves a logit by more than 0.05 (the next test but
    one), ten times the bound."""
    model = EvaByte(_config(param_dtype=jnp.bfloat16))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    probe = Probe(model, params, _serve())
    reqs = [probe.engine.submit(_prompt(n, i), new)
            for i, (n, new) in enumerate([(70, 30), (33, 47), (64, 23)])]
    with _attention(impl):
        probe.run()
    worst = 0.0
    for r in reqs:
        got = np.stack(probe.logits[r.rid])[:, :VOCAB]
        worst = max(worst, float(np.abs(
            got - _reference_rows(params, r)).max()))
    assert worst < 0.005, worst


def test_the_check_sees_the_remote_term(model_and_params, monkeypatch):
    """What the tolerances above would catch: with the summaries dropped
    from the softmax the logits move by far more than 1e-4."""
    from deepspeed_tpu.kernels import eva

    model, params = model_and_params
    real = eva.eva_attention_reference

    def window_only(q, ck, cv, tables, q_pos, **kw):
        return real(q, ck, cv, tables, q_pos % kw["window"], **kw)

    monkeypatch.setattr(eva, "eva_attention_reference", window_only)
    probe = Probe(model, params, _serve())
    req = probe.engine.submit(_prompt(70, 0), 20)
    probe.run()
    got = np.stack(probe.logits[req.rid])[:, :VOCAB]
    assert float(np.abs(got - _reference_rows(params, req)).max()) > 0.05


# -- (d) the cache --------------------------------------------------------------


def _kv(**kw):
    # a window of 32 = 8 blocks, and 4 blocks of summary rows for 64 tokens
    plan = toy_plan(1, 2, 8, 4, 64, attention="eva", window=32, chunk=4)
    base = dict(num_blocks=40, prefix_cache=False)
    base.update(kw)
    return PagedKVCache(plan, **base)


def test_two_kinds_of_row_under_one_allocator():
    kv = _kv()
    assert kv.window_blocks == 8 and kv.token_capacity == 64
    assert kv.table_width == 8 + 4 and kv.plan.groups[0].run == "window"
    # bounded footprint: a window of exact blocks + one summary row / block
    assert kv.blocks_needed(10) == 3 + 1
    assert kv.blocks_needed(64) == 8 + 4
    assert kv.blocks_needed(33) == 8 + 2
    table = kv.reserve("a", kv.blocks_needed(64))
    assert (table == 0).all() and kv.blocks_in_use == 0
    assert kv.promised_blocks == 12 and kv.free_blocks == 39 - 12
    kv.extend("a", 0, 8)             # two exact blocks, two summary rows
    assert len(kv.exact_blocks_of("a")) == 2
    assert len(kv.summary_blocks_of("a")) == 1
    assert kv.blocks_in_use == 3 and kv.promised_blocks == 9
    kv.extend("a", 8, 32)
    assert len(kv.exact_blocks_of("a")) == 8
    assert len(kv.summary_blocks_of("a")) == 2
    free = kv.free_blocks
    assert kv.close_window("a") == 8
    assert kv.exact_blocks_of("a") == [] and kv.summary_rows_in_use == 8
    assert len(kv.summary_blocks_of("a")) == 2
    assert kv.free_blocks == free          # given back, but still booked
    assert kv.blocks_in_use == 2 and kv.promised_blocks == 10
    kv.extend("a", 32, 33)
    assert len(kv.exact_blocks_of("a")) == 1
    assert kv.free("a") == 3
    assert kv.free_blocks == 39 and kv.promised_blocks == 0
    assert kv.summary_rows_in_use == 0


def test_a_table_handed_to_a_program_is_never_rewritten():
    """A program reads its table when it RUNS, after the engine's call
    has returned, and on the CPU `jnp.asarray` may alias the host array:
    what `extend` hands out must not change under a later `extend` or
    `close_window` (the prefill chunk that filled a window used to read
    a table `close_window` had already trashed)."""
    kv = _kv()
    kv.reserve("a", kv.blocks_needed(64))
    first = kv.extend("a", 0, 8)
    seen = first.copy()
    second = kv.extend("a", 8, 32)
    assert np.array_equal(first, seen)
    assert np.array_equal(second[:2], first[:2]) and (second[:8] != 0).all()
    seen = second.copy()
    kv.close_window("a")
    assert np.array_equal(second, seen)
    assert (kv.extend("a", 32, 33)[1:8] == 0).all()


def test_reserve_refuses_what_the_pool_cannot_promise():
    kv = _kv(num_blocks=20)
    assert kv.reserve("a", 12) is not None
    assert kv.reserve("b", 12) is None      # 19 - 12 promised < 12
    assert kv.reserve("b", 7) is not None
    with pytest.raises(ValueError, match="already holds"):
        kv.reserve("a", 1)


def test_windowed_cache_refuses_prefix_cache_and_describes_both_rows():
    with pytest.raises(ValueError, match="prefix cache"):
        _kv(prefix_cache=True)
    text = _kv().describe()
    assert "exact rows for a window of 32 tok (8 blocks)" in text
    assert "summary rows 1 per 4 tok (4 blocks)" in text
    assert "exact rows" in PagedKVCache(
        toy_plan(1, 2, 8, 4, 16), 8, prefix_cache=False).describe()


@IMPLS
def test_cache_invariants_hold_at_every_step(model_and_params, impl):
    """Exact and summary rows in the one pool `[rows, pool_width(H,
    Dh)]`, whichever attention core reads it: blocks come back at a
    window's close and are handed out again, and the requests that took
    them decode what a pool of their own gives (a block a neighbour
    returned holds its stale rows: they are masked, or never walked)."""
    from deepspeed_tpu.serving.kv_cache import pool_width

    model, params = model_and_params
    cfg = _serve(num_blocks=64)
    lens = [(70, 30), (64, 24), (33, 47), (5, 75), (31, 2)]
    with _attention(impl):
        alone = [ServeEngine(model, params, _serve()).generate(
            [_prompt(n, i)], new)[0] for i, (n, new) in enumerate(lens[:2])]
    eng = ServeEngine(model, params, cfg)
    kv = eng.kv
    assert all(c.shape == (64 * C, pool_width(4, 16)) == (256, 128)
               for layer in kv.caches for c in layer)
    free0 = kv.free_blocks
    bound = W // C + cfg.prefill_chunk // C
    reqs = [eng.submit(_prompt(n, i), new) for i, (n, new) in enumerate(lens)]
    while eng.has_work():
        with _attention(impl):
            eng.step()
        live = eng.scheduler.occupied()
        # a request whose last step is launched and unread opens no
        # further window: it gives everything back when it is read
        ahead = [r for r in live if r.cached_len <
                 len(r.prompt) + r.max_new_tokens - 1]
        for r in ahead:
            exact = kv.exact_blocks_of(r.rid)
            assert len(exact) <= bound
            # a closed window's blocks are back before the next step:
            # only the open window's written offsets hold blocks
            assert len(exact) == -(-(r.cached_len % W) // C)
            assert len(kv.summary_blocks_of(r.rid)) == \
                -(-(r.cached_len // C) // C)
            assert len(set(exact) & set(kv._free)) == 0
        assert kv.summary_rows_in_use == sum(
            r.cached_len // W * (W // C) for r in ahead) + sum(
            (r.cached_len - 1) // W * (W // C) for r in live
            if r not in ahead)
        assert kv.blocks_in_use == sum(
            len(kv.blocks_of(r.rid)) for r in live)
        assert kv.free_blocks >= 0
    assert all(r.state == "finished" for r in reqs)
    assert kv.free_blocks == free0 and kv.promised_blocks == 0
    assert sorted(kv._free) == list(range(1, cfg.num_blocks))
    assert eng.peak_blocks_in_use <= 3 * (W // C + 4)
    assert [r.out for r in reqs[:2]] == alone


def test_a_window_closes_under_a_step_in_flight_and_its_blocks_are_retaken(
        model_and_params):
    """The decode step that fills a request's window is launched, the
    window's blocks go back to the free list from positions alone, and
    the step is still unread when a second request's first chunk takes
    them: that chunk is queued behind the step that last read them, so
    both requests get what they get served alone."""
    model, params = model_and_params
    lens = [(29, 20), (12, 30)]
    alone = [ServeEngine(model, params, _serve()).generate(
        [_prompt(n, i)], new)[0] for i, (n, new) in enumerate(lens)]
    eng = ServeEngine(model, params, _serve())
    kv = eng.kv
    before = COUNTERS.snapshot()
    a = eng.submit(_prompt(29, 0), 20)
    held = []
    while a.cached_len < W:
        held = kv.exact_blocks_of(a.rid)
        eng.step()
    # rows 29..31 were decoded for; the last of those steps is unread
    assert len(eng._unread) == 1 and len(a.out) == 3
    assert len(held) == W // C and not kv.exact_blocks_of(a.rid)
    assert COUNTERS.delta_since(before)["kv.window_closes"] == {
        "calls": 1, "bytes": W // C}
    b = eng.submit(_prompt(12, 1), 30)
    eng.step()                              # b's first chunk of 8 rows
    taken = kv.exact_blocks_of(b.rid)
    assert len(taken) == 2 and set(taken) <= set(held)
    eng.run()
    assert [a.out, b.out] == alone
    assert kv.blocks_in_use == 0 and kv.promised_blocks == 0


def test_admission_is_by_bounded_footprint(model_and_params):
    """A request of 200 tokens needs 50 exact blocks unbounded; its
    bounded footprint is 8 + 13.  A pool of 30 admits one such request
    and keeps the second waiting until the first has finished."""
    model, params = model_and_params
    eng = ServeEngine(model, params, _serve(num_blocks=31))
    a = eng.submit(_prompt(150, 0), 50)
    b = eng.submit(_prompt(150, 1), 50)
    assert eng.scheduler.blocks_reserved(a) == 8 + 13
    eng.step()
    assert a.state != "waiting" and b.state == "waiting"
    while not a.done:
        eng.step()
        assert b.state == "waiting" or a.done
    eng.run()
    assert a.state == b.state == "finished" and len(b.out) == 50
    assert eng.kv.free_blocks == 30
    with pytest.raises(ValueError, match="only has"):
        ServeEngine(model, params, _serve(num_blocks=16)).submit(
            _prompt(150, 0), 50)


def test_counters_and_the_window_close_span(model_and_params, tmp_path):
    from deepspeed_tpu.monitor.tracing import TraceRecorder

    model, params = model_and_params
    eng = ServeEngine(model, params, _serve())
    rec = TraceRecorder(str(tmp_path), buffer_events=4096, sample_rate=1.0)
    eng.attach_tracing(tracer=rec)
    before = COUNTERS.snapshot()
    req = eng.submit(_prompt(30, 0), 41)        # cached length reaches 70
    eng.run()
    d = COUNTERS.delta_since(before)
    assert d["kv.window_closes"] == {"calls": 2, "bytes": 16}
    assert d["kv.summary_rows"]["bytes"] == 70 // C
    # decode queries at positions 30..69: window offsets + 1, plus eight
    # summary rows a closed window
    want = sum(p % W + 1 + p // W * (W // C) for p in range(30, 70))
    assert d["serve.eva.rows_read"] == {"calls": 40, "bytes": want}
    assert d["serve.eva.context_tokens"] == {
        "calls": 40, "bytes": sum(range(31, 71))}
    # the oracle (every backend but the TPU) gathers the whole table
    assert d["serve.eva.rows_walked"] == {
        "calls": 40, "bytes": 40 * eng.kv.table_width * C}
    spans = [e for e in rec.last_events()
             if e.get("name") == "eva.window_close"]
    assert [e["args"]["cached"] for e in spans] == [32, 64]
    assert all(e["args"]["blocks"] == 8 and e["ph"] == "X" for e in spans)
    rec.close()
    assert req.state == "finished"


def test_rows_walked_counts_live_blocks_where_the_kernel_runs(monkeypatch):
    """`serve.eva.rows_walked` is what the registry answers for the
    decode program's shapes, asked once at build: where it picks the
    kernel (on the chip, blocks of whole tiles), a query's live window
    blocks plus its live summary blocks, whole."""
    from deepspeed_tpu.ops import pallas_backend

    window, chunk = 64, 8
    model = EvaByte(_config(window_size=window, chunk_size=chunk))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    serve = _serve(block_size=chunk, prefill_chunk=16, num_blocks=64)
    with monkeypatch.context() as chip:
        chip.setattr(pallas_backend, "interpret", lambda: False)
        eng = ServeEngine(model, params, serve)
    assert eng._counted["eva"].walks == ((True, None),)
    assert ServeEngine(model, params, serve)._counted["eva"].walks == (
        (False, None),)
    before = COUNTERS.snapshot()
    with _attention("pallas"):
        out = eng.generate([_prompt(60, 0)], 81)[0]   # positions 60..139
    d = COUNTERS.delta_since(before)
    blocks = lambda p: p % window // chunk + 1 + \
        -(-(p // window * (window // chunk)) // chunk)
    assert d["serve.eva.rows_walked"] == {
        "calls": 80, "bytes": chunk * sum(blocks(p) for p in range(60, 140))}
    assert d["serve.eva.rows_read"]["bytes"] <= \
        d["serve.eva.rows_walked"]["bytes"] < 80 * eng.kv.table_width * chunk
    with _attention("jnp"):
        assert out == ServeEngine(model, params, serve).generate(
            [_prompt(60, 0)], 81)[0]


def test_nothing_compiles_after_the_warm_up_call(model_and_params):
    """The benchmark's runner warms up with one prompt of prefill_chunk
    + 1 tokens and 2 new tokens and counts any later backend compile as
    an incorrect run: window close must need no program of its own."""
    import jax.monitoring

    model, params = model_and_params
    eng = ServeEngine(model, params, _serve())
    eng.generate([_prompt(eng.config.prefill_chunk + 1, 0)], 2)
    compiles = []

    def listen(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        outs = eng.generate([_prompt(70, 1), _prompt(33, 2)], 40)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert [len(o) for o in outs] == [40, 40] and compiles == []


# -- (e) what the engine refuses for this family ---------------------------------


@pytest.mark.parametrize("kw,error,match", [
    (dict(prefix_cache=True), NotImplementedError, "prefix_cache=True"),
    (dict(draft_len=2), NotImplementedError, "draft_len > 0"),
    (dict(quantized_weights="int8"), NotImplementedError,
     "quantized_weights"),
    (dict(kv_dtype="int8"), NotImplementedError, "kv_dtype 'int8'"),
    (dict(kv_dtype="int4"), NotImplementedError, "kv_dtype 'int4'"),
    (dict(block_size=8), ValueError, "chunk_size"),
    (dict(prefill_chunk=12), ValueError, "divide"),
    (dict(prefill_chunk=6), ValueError, "whole chunks"),
], ids=["prefix_cache", "drafting", "quantized_weights", "int8_kv",
        "int4_kv", "block_size", "prefill_chunk", "prefill_chunk_ragged"])
def test_engine_refuses_by_name(model_and_params, kw, error, match):
    model, params = model_and_params
    with pytest.raises(error, match=match):
        ServeEngine(model, params, _serve(**kw))


def test_sessions_are_refused_by_name(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, _serve())
    with pytest.raises(NotImplementedError, match="sessions"):
        eng.submit(_prompt(10), 4, session_id="s")
    assert not eng.has_work()


def test_schedule_describes_both_kinds_of_row_and_the_registry_has_the_op(
        model_and_params):
    from deepspeed_tpu.kernels import registry

    model, _ = model_and_params
    sched = ServeSchedule(max_batch=2, prefill_chunk=8, block_size=C,
                          num_blocks=32, table_width=8 + 16,
                          window_blocks=8)
    text = sched.describe()
    assert "exact rows for a window of 8 blocks" in text
    assert "16 blocks of summary rows, 1 per 4 tok" in text
    assert "per-request cap 256" in text
    ServeProgramBuilder(model, sched)
    with pytest.raises(ValueError, match="window_blocks"):
        ServeProgramBuilder(model, sched._replace(window_blocks=4))
    assert registry.resolve_impl("eva_attention") == "jnp"   # not a TPU
    with pytest.raises(RuntimeError, match="impl='pallas' forced"):
        registry.resolve_impl("eva_attention", impl="pallas")
    spec = model.layer_spec()
    assert (spec.attention, spec.window, spec.chunk) == ("eva", W, C)


def test_a_spec_serving_has_no_block_for_is_refused_by_name():
    from deepspeed_tpu.serving import layers

    spec = EvaByte(_config()).layer_spec()
    with pytest.raises(NotImplementedError, match="'learned' positions"):
        layers.check_spec(spec._replace(positions="learned"))
    with pytest.raises(ValueError, match="not one of"):
        layers.check_spec(spec._replace(norm="batchnorm"))
