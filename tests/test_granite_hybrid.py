"""Granite 4.0-H through the serving engine: Mamba-2 state-space layers
that keep a fixed state a slot beside grouped-attention layers without
positions, the four scalars on the stream — against the plain reference
(`benchmarks/reference/granite_hybrid.py`, the recurrence only) on seeded
weights at toy widths: 6 layers (two periods of 3, attention at 1 and 4),
hidden 32, 4 query heads on 2 K/V heads of 8, 4 state-space heads of 8
over a state of 16, convolution 4, scan chunk 4, vocabulary 97.
"""

import contextlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import granite_hybrid as ref
from deepspeed_tpu.models import (GraniteHybrid, GraniteHybridConfig,
                                  LayerSpec)
from deepspeed_tpu.models import granite_hybrid as gh
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (PagedKVCache, ServeConfig, ServeEngine,
                                   ServeProgramBuilder, ServeSchedule)
from deepspeed_tpu.serving import layers as serving_layers
from deepspeed_tpu.serving.kv_cache import cache_plan
from toy_plans import toy_plan

VOCAB, LAYERS, PERIOD, AT = 97, 6, 3, (1,)
HEADS, KV, DH = 4, 2, 8
SH, SP, SN, TAPS, SCAN = 4, 8, 16, 4, 4
BS, CHUNK, SEQ = 4, 8, 64
STATE_LAYERS = (0, 2, 3, 5)


def _config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=SEQ, num_layers=LAYERS,
                period=PERIOD, attention_at=AT, d_model=32, d_ffn=64,
                num_heads=HEADS, kv_heads=KV, head_dim=DH, ssm_heads=SH,
                ssm_head_dim=SP, ssm_state=SN, ssm_conv=TAPS,
                ssm_chunk=SCAN, init_std=0.2)
    base.update(kw)
    return GraniteHybridConfig(**base)


def _kw(cfg):
    return dict(mixers=tuple("attention" if cfg.attends(i) else "mamba"
                             for i in range(cfg.num_layers)),
                heads=cfg.num_heads, kv_heads=cfg.kv_heads,
                ssm_heads=cfg.ssm_heads, state=cfg.ssm_state,
                eps=cfg.rms_norm_eps, embed=cfg.embedding_multiplier,
                residual=cfg.residual_multiplier,
                attn=cfg.attention_multiplier, divisor=cfg.logits_scaling)


def _serve(**kw):
    base = dict(block_size=BS, num_blocks=64, max_batch=3,
                prefill_chunk=CHUNK, max_seq_len=SEQ, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


def _model(dtype=jnp.float32, **kw):
    model = GraniteHybrid(_config(param_dtype=dtype, **kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


_BUILT = {}


def _engine(model, params, **kw):
    """An engine on programs built once for each (model config, serve
    config): the weights are arguments, so engines share them."""
    from deepspeed_tpu.kernels import get_kernel_config

    serve = _serve(**kw)
    # a program keeps the kernels it was traced with
    key = (repr(model.config), repr(serve), repr(get_kernel_config()))
    eng = ServeEngine(model, params, serve, programs=_BUILT.get(key))
    _BUILT[key] = eng.programs
    return eng


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


def _ref_logits(model, params, tokens):
    """The reference's logits at every position of `tokens`: at the one
    width `SEQ` (causal: what stands behind a position does not reach
    it), so the reference compiles once."""
    padded = np.zeros((1, SEQ), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(ref.logits(params, jnp.asarray(padded),
                                 **_kw(model.config)))[0, :len(tokens)]


# the logits have a standard deviation of 0.012 (the tied head over
# `logits_scaling` on rows of std / `embedding_multiplier`), so the
# limits are in those units.  float32: the largest difference — the
# chunked scan sums a chunk's inputs in another order than the
# recurrence, through six layers.  bf16: the mean difference — the inputs
# of every product rounded to 8 bits of mantissa, the convolution's kept
# inputs too (2 % of a standard deviation through the engine)
TOL = {"float32": 2e-6, "bfloat16": 6e-4}


def _differ(got, want, dtype):
    d = np.abs(np.asarray(got, np.float32) - want)
    return d.max() if dtype == "float32" else d.mean()


# -- the uncached forward against the reference -------------------------------


@pytest.mark.parametrize("length", [1, 2, 3, 9, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_plain_reference(dtype, length):
    """`apply` scans whole chunks of 4 (padded past the sequence); the
    reference steps the recurrence: lengths under the convolution's 3
    kept inputs, under a chunk, not whole chunks, whole chunks."""
    model, params = _model(jnp.dtype(dtype))
    tokens = _prompt(length, length)
    got = model.apply(params, jnp.asarray([tokens]))[0]
    want = _ref_logits(model, params, tokens)
    assert want.std() > 0.005
    assert _differ(got, want, dtype) < TOL[dtype]


def test_reference_is_independent_of_the_model_under_test():
    import inspect

    src = inspect.getsource(ref)
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    assert "cumsum" not in src and "lax.scan" in src  # recurrence only


def test_the_published_pattern_puts_attention_at_5_15_25_35():
    model = GraniteHybrid(GraniteHybridConfig())
    spec = model.layer_spec()
    attends = [i for i in range(40) if spec.mixer_of(i) == "attention"]
    assert attends == [5, 15, 25, 35]
    assert len(spec.state_layers(40)) == 36 and spec.has_state
    assert spec.ssm_conv_width == 4352 and spec.positions == "none"
    assert (spec.embed_scale, spec.residual_scale, spec.attn_scale,
            spec.logit_divisor) == (12.0, 0.22, 0.015625, 8.0)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert model.num_params(shapes) == 3_191_396_096
    assert ("ssm" in shapes["blocks"][4]) and ("attn" in shapes["blocks"][5])


def test_seeded_heads_remember_from_a_few_to_thousands_of_tokens():
    """A head forgets over 1 / (D A) tokens: with -A in [1, 16] and D in
    [0.001, 0.1] from under one token to a thousand — a state lost at a
    chunk boundary then shows in the logits, which a decay of one half a
    token (a normal draw) would hide."""
    _, params = _model()
    p = params["blocks"][0]["ssm"]
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    rate = step * np.exp(np.asarray(p["A_log"]))
    assert (0.001 <= step).all() and (step <= 0.1).all()
    assert (1.0 <= np.exp(p["A_log"])).all() and (rate < 1.6).all()
    assert np.asarray(p["D"]).tolist() == [1.0] * SH
    # the convolution's taps as a depthwise Conv1d draws them: at the
    # matrices' 0.2 the recurrence is lost beside the skip D x
    taps = np.asarray(p["conv_w"])
    assert np.abs(taps).max() <= TAPS ** -0.5 and taps.std() > 0.25


# -- the two forms of the mixer -----------------------------------------------


def _mixer_inputs(T, seed=0, B=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, T, SH, SP))
    Bm = jax.random.normal(k[1], (B, T, SN))
    Cm = jax.random.normal(k[2], (B, T, SN))
    dt = jax.random.uniform(k[3], (B, T, SH), minval=0.01, maxval=0.5)
    A = -jax.random.uniform(k[4], (SH,), minval=0.5, maxval=4.0)
    state = jax.random.normal(k[5], (B, SH, SP, SN))
    return x, Bm, Cm, dt, A, state


def _by_steps(x, Bm, Cm, dt, A, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = gh.ssm_step(x[:, t], Bm[:, t], Cm[:, t], dt[:, t], A,
                               state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("T,chunk", [(4, 4), (8, 4), (12, 2), (6, 6),
                                     (16, 1)])
def test_the_chunked_scan_is_the_recurrence(T, chunk):
    """From a state that is not zero: outputs and the state left behind,
    one chunk and several."""
    args = _mixer_inputs(T)
    y, state = gh.ssm_scan(*args, chunk)
    want_y, want_state = _by_steps(*args)
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-4, rtol=1e-5)


def _mix(model, params, h, state, conv, n_valid):
    return gh.ssm_mix(model.layer_spec(), params["blocks"][0]["ssm"], h,
                      state, conv, jnp.asarray(n_valid, jnp.int32))


@pytest.mark.parametrize("n_valid", [0, 1, 2, 3, 5, 8])
def test_a_padded_tail_moves_neither_state_nor_convolution_inputs(n_valid):
    """A chunk of 8 with `n_valid` real positions leaves what the valid
    prefix alone leaves — the state, and the convolution's last three
    VALID inputs, also where the chunk has fewer than three — whatever
    stands in the padding."""
    model, params = _model()
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    h = jax.random.normal(k[0], (1, 8, 32))
    state = jax.random.normal(k[1], (1, SH, SP, SN))
    conv = jax.random.normal(k[2], (1, TAPS - 1, SH * SP + 2 * SN))
    out, s1, c1 = _mix(model, params, h, state, conv, [n_valid])
    # the same valid prefix, other padding
    other = h.at[:, n_valid:].set(jax.random.normal(k[3], (1, 8 - n_valid,
                                                           32)))
    out2, s2, c2 = _mix(model, params, other, state, conv, [n_valid])
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_array_equal(out[:, :n_valid], out2[:, :n_valid])
    # token by token through the recurrence's form
    s, c = state, conv
    for t in range(n_valid):
        _, s, c = _mix(model, params, h[:, t:t + 1], s, c, [1])
    np.testing.assert_allclose(s1, s, atol=1e-5)
    np.testing.assert_allclose(c1, c, atol=1e-6)   # a product of 8 rows
    if n_valid == 0:                               # against 8 of one
        np.testing.assert_array_equal(s1, state)
        np.testing.assert_array_equal(c1, conv)


def test_a_decode_step_has_no_term_across_slots():
    """Three slots, the middle one not running: its state and its
    convolution inputs come back bit for bit, and the running slots'
    outputs and states do not depend on what the others hold."""
    model, params = _model()
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    h = jax.random.normal(k[0], (3, 1, 32))
    state = jax.random.normal(k[1], (3, SH, SP, SN))
    conv = jax.random.normal(k[2], (3, TAPS - 1, SH * SP + 2 * SN))
    out, s1, c1 = _mix(model, params, h, state, conv, [1, 0, 1])
    np.testing.assert_array_equal(s1[1], state[1])
    np.testing.assert_array_equal(c1[1], conv[1])
    assert not np.array_equal(s1[0], state[0])
    # other neighbours, the same slots 0 and 2
    h2 = h.at[1].set(jax.random.normal(k[3], (1, 32)))
    state2 = state.at[1].set(jax.random.normal(k[4], (SH, SP, SN)))
    out2, s2, c2 = _mix(model, params, h2, state2, conv * jnp.asarray(
        [1.0, -3.0, 1.0])[:, None, None], [1, 1, 1])
    for slot in (0, 2):
        np.testing.assert_array_equal(out[slot], out2[slot])
        np.testing.assert_array_equal(s1[slot], s2[slot])
        np.testing.assert_array_equal(c1[slot], c2[slot])


# -- the recurrence over the live slots (kernels/ssm.py) ----------------------


def _forced():
    """The registry's own override: the `ssm_step` kernel, under the
    Pallas interpreter."""
    from deepspeed_tpu.kernels import kernel_config

    return kernel_config(ops={"ssm_step": "pallas"}, interpret=True)


@pytest.mark.parametrize("live", [
    (0, 0, 0, 0, 0, 0), (0, 0, 0, 1, 0, 0), (1, 1, 1, 1, 1, 1),
    (1, 0, 1, 1, 0, 1), (0, 1, 0, 0, 1, 0), (0, 0, 0, 0, 0, 1)],
    ids=["none", "one", "all", "scattered", "two", "last"])
@pytest.mark.parametrize("tiles", [1, 2])
def test_the_kernel_steps_the_live_slots_and_no_others(live, tiles,
                                                       monkeypatch):
    """Against `ssm_step` under dt = 0 for a slot that is not live: the
    live slots' state and y to float32 tolerance, every other slot's
    state the input's bit for bit and its y zeros — with a slot's state
    as one block, and as two tiles of heads."""
    from deepspeed_tpu.kernels import registry, ssm

    if tiles > 1:
        monkeypatch.setattr(ssm, "_STATE_BLOCK_BYTES",
                            4 * (SH // tiles) * SP * SN * 4)
    assert ssm.head_tile(SH, SP, SN) == SH // tiles
    x, Bm, Cm, dt, A, state = _mixer_inputs(1, B=6)
    x, Bm, Cm, dt = (a[:, 0] for a in (x, Bm, Cm, dt))
    on = np.asarray(live, bool)
    dt = dt * jnp.asarray(on, jnp.float32)[:, None]
    ids, n = ssm.live_slots(jnp.asarray(live))
    assert int(n) == on.sum()
    assert list(np.asarray(ids)[:on.sum()]) == list(np.flatnonzero(on))
    assert set(np.asarray(ids)[on.sum():]) <= {
        int(np.flatnonzero(on)[-1]) if on.any() else 0}
    want_y, want_state = gh.ssm_step(x, Bm, Cm, dt, A, state)
    with _forced():
        y, got = jax.jit(lambda *a: registry.dispatch(
            "ssm_step", *a, info=ssm.ssm_step_info(state)))(
                x, Bm, Cm, dt, A, state, ids, n)
    y, got = np.asarray(y), np.asarray(got)
    np.testing.assert_array_equal(got[~on], np.asarray(state)[~on])
    np.testing.assert_array_equal(y[~on], 0.0)
    np.testing.assert_allclose(got[on], np.asarray(want_state)[on],
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(y[on], np.asarray(want_y)[on], atol=1e-5,
                               rtol=1e-5)


def test_the_kernel_is_chosen_by_what_the_call_shows():
    """Off a TPU, and for a state that is not whole float32 tiles, the
    recurrence is `ssm_step` itself; forced, the registry says why."""
    from deepspeed_tpu.kernels import registry
    from deepspeed_tpu.kernels.ssm import ssm_step_info

    cell = ssm_step_info(jax.ShapeDtypeStruct((64, 64, 64, 128),
                                              jnp.float32))
    toy = ssm_step_info(jax.ShapeDtypeStruct((3, SH, SP, SN), jnp.float32))
    op = registry.get_kernel("ssm_step")
    assert op.auto_supports("default", cell) == (True, "")
    assert not op.auto_supports("default", toy)[0]
    assert registry.resolve_impl("ssm_step", info=cell) == "jnp"   # the CPU
    with pytest.raises(RuntimeError, match="backend is 'cpu'"):
        registry.resolve_impl("ssm_step", impl="pallas", info=cell)


def test_a_decode_step_has_no_term_across_slots_through_the_kernel():
    with _forced():
        test_a_decode_step_has_no_term_across_slots()


# -- through the programs -----------------------------------------------------


def _drive(model, params, prompt, n_decode, chunk, slot=1, slots=3):
    """One request by hand through a builder's programs: prefill chunk
    by chunk into slot `slot`, then decode steps -> (every logits row,
    the caches)."""
    W = SEQ // BS
    sched = ServeSchedule(max_batch=slots, prefill_chunk=chunk,
                          block_size=BS, num_blocks=64, table_width=W)
    spec = model.layer_spec()
    plan = cache_plan(spec, model.config,
                      _serve(max_batch=slots, prefill_chunk=chunk))
    assert plan.table_width == W and plan.groups[0].arrays == (
        ((SH, SP, SN), "float32"), ((TAPS - 1, spec.ssm_conv_width), None))
    kv = PagedKVCache(plan, 64, prefix_cache=False)
    key = (repr(model.config), sched)
    if key not in _BUILT:
        builder = ServeProgramBuilder(model, sched)
        _BUILT[key] = builder.build(), jax.jit(builder.step_logits)
    progs, step = _BUILT[key]
    table = kv.alloc("r", -(-(len(prompt) + n_decode) // BS))
    rows, caches = [], kv.caches
    zero = (np.float32(0), np.int32(0), np.uint32(0))
    for pos in range(0, len(prompt), chunk):
        part = prompt[pos:pos + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(part)] = part
        tok, lg, caches = progs["prefill"](
            params, caches, jnp.asarray(toks), np.int32(pos),
            np.int32(len(part)), jnp.asarray(np.append(table, slot)), *zero)
    rows.append(np.asarray(lg))
    tok = int(tok)
    active = np.arange(slots) == slot
    tables = np.zeros((slots, W), np.int32)
    tables[slot] = table
    for p in range(len(prompt), len(prompt) + n_decode):
        lg, caches, _ = step(
            params, caches, jnp.full((slots,), tok, jnp.int32),
            jnp.full((slots,), p, jnp.int32), jnp.asarray(active),
            jnp.asarray(tables))
        rows.append(np.asarray(lg[slot]))
        tok = int(np.argmax(lg[slot]))
    return np.stack(rows), caches


@pytest.mark.parametrize("length,chunk", [
    (2, 8), (3, 8), (8, 8), (11, 8), (16, 8), (21, 8),   # 8: two scan chunks
    (7, 4), (13, 4),                                     # one scan chunk
    (9, 2), (5, 1)])                                     # under a scan chunk
def test_prefill_in_chunks_then_decode_is_the_reference_forward(length,
                                                                chunk):
    """A prompt through chunks that do and do not divide it, shorter
    than the convolution's three kept inputs among them, then five
    decode steps: every logits row is the reference's full forward's,
    so the state and the convolution inputs crossed every boundary."""
    model, params = _model()
    prompt = _prompt(length, length)
    got, _ = _drive(model, params, prompt, 5, chunk)
    tokens = list(prompt)
    for i in range(6):
        want = _ref_logits(model, params, tokens)[-1]
        assert np.abs(got[i] - want).max() < TOL["float32"], (i, length)
        tokens.append(int(np.argmax(got[i])))


def test_the_state_left_behind_does_not_depend_on_the_chunking():
    """The same prompt through chunks of 8, 4 and 1: the slot's state
    agrees to float32 rounding, the convolution's kept inputs bit for
    bit, and the other slots' entries are still zero."""
    model, params = _model()
    prompt = _prompt(13, 5)
    _, a = _drive(model, params, prompt, 0, 8)
    for chunk in (4, 1):
        _, b = _drive(model, params, prompt, 0, chunk)
        for i in STATE_LAYERS:
            np.testing.assert_allclose(a[i][0][1], b[i][0][1], atol=1e-5)
            np.testing.assert_allclose(a[i][1][1], b[i][1][1], atol=1e-5)
    for i in STATE_LAYERS:
        assert np.abs(a[i][0][1]).max() > 0
        for other in (0, 2):
            assert not np.asarray(a[i][0][other]).any()
            assert not np.asarray(a[i][1][other]).any()


def test_a_prefill_chunk_must_be_whole_scan_chunks():
    model, _ = _model()
    sched = ServeSchedule(max_batch=1, prefill_chunk=6, block_size=BS,
                          num_blocks=16, table_width=SEQ // BS)
    with pytest.raises(ValueError, match="whole chunks of it"):
        ServeProgramBuilder(model, sched)
    ServeProgramBuilder(model, sched._replace(prefill_chunk=2))  # under one


# -- through the engine --------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_matches_the_reference_forward(dtype):
    """Seven requests through three slots (slots are reused mid-run, the
    loop a step ahead), prompts from 1 token to three chunks: at every
    generated position the logits the engine drew from are the
    reference's full forward's."""
    from test_evabyte import Probe

    model, params = _model(jnp.dtype(dtype))
    probe = Probe(model, params, _serve())
    eng = probe.engine
    lengths = [1, 2, 5, 8, 13, 17, 24]
    reqs = [eng.submit(_prompt(n, i), 4 + i) for i, n in enumerate(lengths)]
    probe.run()
    assert [r.state for r in reqs] == ["finished"] * len(reqs)
    assert eng.kv.blocks_in_use == 0
    for r in reqs:
        lg = _ref_logits(model, params, r.prompt + r.out)
        first = len(r.prompt) - 1
        want = lg[first:first + len(r.out)]
        got = np.stack(probe.logits[r.rid])[:len(r.out)]
        assert _differ(got, want, dtype) < TOL[dtype], r.rid
        chosen = want[np.arange(len(r.out)), r.out]
        assert (want.max(-1) - chosen).mean() <= (0 if dtype == "float32"
                                                  else TOL[dtype])


def test_prefill_then_decode_matches_the_reference_forward_through_the_kernel():
    with _forced():
        test_prefill_then_decode_matches_the_reference_forward("float32")


def _alone(model, params, prompt, n, **kw):
    return _engine(model, params, **kw).generate([prompt], n)[0]


def test_a_request_does_not_depend_on_its_neighbours():
    """Continuous batching: six requests through three slots, joining
    and leaving mid-run — each one's tokens are those it gets alone."""
    model, params = _model()
    prompts = [_prompt(n, 10 + i) for i, n in enumerate((3, 19, 9, 1, 12, 6))]
    news = [9, 4, 12, 7, 3, 10]
    eng = _engine(model, params)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
    eng.run()
    for r, p, n in zip(reqs, prompts, news):
        assert r.out == _alone(model, params, p, n), r.rid


def test_a_slots_last_tenant_does_not_leak(monkeypatch):
    """One slot, four requests one after another, each ending on an
    `eos_token` the loop finds a step late — the retired slot is stepped
    once more — and the next request is seated in it at once: each
    answer is the one the request gets in a fresh engine.  Without the
    zeroing at seating it is not."""
    model, params = _model()
    prompts = [_prompt(n, 20 + i) for i, n in enumerate((9, 2, 13, 5))]
    full = [_alone(model, params, p, 6, max_batch=1) for p in prompts]
    eos = [out[2] for out in full]     # its own third token ends an answer
    want = [out[:out.index(e) + 1] for out, e in zip(full, eos)]

    def serve_all():
        eng = _engine(model, params, max_batch=1)
        before = COUNTERS.snapshot()
        reqs = [eng.submit(p, 6, eos_token=e) for p, e in zip(prompts, eos)]
        eng.run()
        return [r.out for r in reqs], COUNTERS.delta_since(before)

    outs, d = serve_all()
    assert outs == want
    assert d["serve.ssm.state_resets"]["calls"] == 4
    assert d["serve.decode_ahead.dropped"]["calls"] >= 1
    monkeypatch.setattr(PagedKVCache, "reset_state", lambda self, slot: None)
    assert serve_all()[0] != want


def test_a_slots_last_tenant_does_not_leak_through_the_kernel(monkeypatch):
    with _forced():
        test_a_slots_last_tenant_does_not_leak(monkeypatch)


def test_the_cache_holds_a_state_a_slot_beside_rows():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    kv = eng.kv
    conv = SH * SP + 2 * SN
    for i, entry in enumerate(kv.caches):
        if i in STATE_LAYERS:
            assert [a.shape for a in entry] == [(3, SH, SP, SN),
                                                (3, TAPS - 1, conv)]
            assert entry[0].dtype == jnp.float32
        else:
            assert [a.shape for a in entry] == [(64 * BS, 128)] * 2
    state = 4 * 3 * (SH * SP * SN * 4 + (TAPS - 1) * conv * 4)
    assert kv.state_nbytes() == state
    assert kv.nbytes() == state + 2 * 2 * 64 * BS * 128 * 4
    assert kv.bytes_per_block() == 2 * 2 * BS * 128 * 4   # two layers' rows
    assert "4 layer(s) with no rows and a state a slot, 3 slots" in \
        kv.describe()
    # a request's blocks are the attention layers' alone, and `free`
    # leaves the state; `reset_state` zeroes one slot's and no other's
    eng.generate([_prompt(9, 1), _prompt(5, 2)], 3)
    assert kv.blocks_in_use == 0
    held = [np.abs(np.asarray(kv.caches[0][0][s])).max() for s in range(3)]
    assert held[0] > 0 and held[1] > 0 and held[2] == 0
    kv.reset_state(1)
    assert np.abs(np.asarray(kv.caches[0][0][0])).max() == held[0]
    for i in STATE_LAYERS:
        assert not np.asarray(kv.caches[i][0][1]).any()
        assert not np.asarray(kv.caches[i][1][1]).any()


def test_a_cache_with_a_state_refuses_what_it_cannot_hold():
    plan = toy_plan(3, 2, 8, 4, 16, slots=2, attention="grouped", kv_heads=2,
                    layer_mixers=("ssm", "attention", "ssm"), ssm_heads=2,
                    ssm_head_dim=2, ssm_state=4, ssm_conv=4)
    assert [(g.keeps, g.layers) for g in plan.groups] == [
        ("slots", (0, 2)), ("blocks", (1,))]
    PagedKVCache(plan, 8, prefix_cache=False)
    mesh = types.SimpleNamespace(size=2, axis_size=lambda axis: 1)
    for change in (dict(prefix_cache=True), dict(dtype="int8"),
                   dict(mesh_info=mesh)):
        with pytest.raises(ValueError, match="keep a state a slot"):
            PagedKVCache(plan, 8, **dict(dict(prefix_cache=False), **change))
    # (what a slot keeps and how many slots are the spec's and the
    # ServeConfig's: a plan has both or no such group; and windows beside
    # a state are a mask on the table, never a ring)
    assert toy_plan(3, 2, 8, 4, 64, slots=2, attention="grouped", kv_heads=2,
                    layer_mixers=("ssm", "attention", "ssm"), ssm_heads=2,
                    ssm_head_dim=2, ssm_state=4, ssm_conv=4,
                    layer_windows=(0, 8, 0)).ring_blocks == 0


@pytest.mark.parametrize("way", ["oracle", "kernel"])
def test_counters_of_the_state_space_layers(way):

    model, params = _model()
    with _forced() if way == "kernel" else contextlib.nullcontext():
        eng = ServeEngine(model, params, _serve())
        before = COUNTERS.snapshot()
        lengths = (8, 19)
        eng.generate([_prompt(n, i) for i, n in enumerate(lengths)], 6)
    d = COUNTERS.delta_since(before)
    steps = d["serve.decode_steps"]["calls"]
    assert d["serve.ssm.state_resets"] == {"calls": 2, "bytes": 0}
    # chunks of 8: one, and three
    assert d["serve.prefill_chunks"] == {"calls": 4, "bytes": 27}
    # as the program is built.  The oracle: every step streams every
    # slot's state, in and out, live or not.  The kernel: the float32
    # state of the running slots (10 over the steps, of 3 a step) and
    # every slot's convolution inputs — any other slot's state, in 4
    # layers, is never touched
    every = 2 * eng.kv.state_nbytes()
    dead = 2 * len(STATE_LAYERS) * SH * SP * SN * 4 if way == "kernel" else 0
    assert d["serve.ssm.state_bytes"] == {
        "calls": steps, "bytes": steps * every - (3 * steps - 10) * dead}
    assert d["kernel.dispatches" if way == "kernel" else "kernel.fallbacks"][
        "calls"] >= len(STATE_LAYERS)
    # 2 requests x 5 decode steps x 4 layers with a state
    assert d["serve.ssm.slots_live"] == {"calls": steps, "bytes": 10 * 4}
    # the 2 attention layers' rows: every cached position
    held = [n + i + 1 for n in lengths for i in range(5)]
    assert d["serve.attn.rows_read"] == {"calls": 10, "bytes": 2 * sum(held)}
    assert "serve.window.rows_read" not in d
    assert "serve.paged.rows_walked" not in d


@pytest.mark.parametrize("way", ["oracle", "kernel"])
def test_rows_walked_is_what_the_attention_layers_fetch(way, chip_rule):
    """`serve.attn.rows_walked` beside `serve.attn.rows_read`: where the
    registry answers the oracle for the decode program's shapes (every
    backend but the TPU) each attention layer gathers the table's whole
    width for every running slot; where it answers the walk, a slot's
    cached length rounded up to a block — and the tokens served are the
    oracle's."""
    model, params = _model()
    # blocks that are whole tiles of float32, a chunk no verify step is
    serve = _serve(block_size=8, num_blocks=32, prefill_chunk=16)
    lengths, bs = (8, 19), 8
    prompts = [_prompt(n, i) for i, n in enumerate(lengths)]
    with chip_rule("grouped_attention") if way == "kernel" \
            else contextlib.nullcontext():
        eng = ServeEngine(model, params, serve)
        before = COUNTERS.snapshot()
        out = eng.generate(prompts, 6)
    d = COUNTERS.delta_since(before)
    assert [w[0] for w in eng._counted["grouped"].walks] == [way == "kernel"]
    attention = LAYERS - len(STATE_LAYERS)
    held = [n + i + 1 for n in lengths for i in range(5)]
    assert d["serve.attn.rows_read"] == {
        "calls": 10, "bytes": attention * sum(held)}
    fetched = sum(-(-h // bs) * bs for h in held) if way == "kernel" \
        else 10 * eng.kv.table_width * bs
    assert d["serve.attn.rows_walked"] == {
        "calls": 10, "bytes": attention * fetched}
    assert d["serve.attn.rows_read"]["bytes"] <= \
        d["serve.attn.rows_walked"]["bytes"]
    if way == "kernel":
        # decode's attention layers, and no other call, took the kernel
        assert d["kernel.dispatches"]["calls"] == attention
        assert out == ServeEngine(model, params, serve).generate(prompts, 6)
    else:
        assert "kernel.dispatches" not in d


def test_seating_is_a_phase_of_the_loop(tmp_path):
    from deepspeed_tpu.monitor.tracing import TraceRecorder

    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    rec = TraceRecorder(str(tmp_path), buffer_events=1 << 12,
                        sample_rate=1.0)
    eng.attach_tracing(tracer=rec)
    eng.generate([_prompt(11, 3)], 3)
    names = [e["name"] for e in rec.last_events()]
    rec.close()        # joins its writer thread: none is left behind
    assert names.count("serve.state.reset") == 1
    assert names.index("serve.state.reset") < names.index("prefill_chunk")


# -- refused, by name ----------------------------------------------------------


@pytest.mark.parametrize("serve,match", [
    (dict(prefix_cache=True), "prefix_cache=True over layers with a state"),
    (dict(draft_len=2), "draft_len > 0 over layers with a state"),
    (dict(kv_dtype="int8"), "kv_dtype 'int8' over layers with a state"),
    (dict(kv_dtype="int4"), "kv_dtype 'int4' over layers with a state"),
    (dict(quantized_weights="int8"),
     "quantized_weights over layers with a state"),
    (dict(quantized_weights="int4"),
     "quantized_weights over layers with a state"),
])
def test_engine_refuses_by_name(serve, match):
    model, params = _model()
    with pytest.raises(NotImplementedError, match=match):
        ServeEngine(model, params, _serve(**serve))


def test_engine_refuses_sessions_and_a_mesh_by_name():
    from deepspeed_tpu.comm import make_mesh

    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    with pytest.raises(NotImplementedError,
                       match="sessions over layers with a state"):
        eng.submit(_prompt(5), 4, session_id="s")
    with pytest.raises(NotImplementedError,
                       match="a mesh of 2 devices over layers with a state"):
        ServeEngine(model, params, _serve(),
                    mesh_info=make_mesh(model=2, data=1,
                                        devices=jax.devices()[:2]))


def test_the_verify_program_is_refused_by_name():
    model, _ = _model()
    with pytest.raises(NotImplementedError, match="rewound"):
        serving_layers.address_grid(model.layer_spec(), None, None, None,
                                    None, None)


# -- the layer spec -----------------------------------------------------------


def _spec(**kw):
    return _model()[0].layer_spec()._replace(**kw)


@pytest.mark.parametrize("change,match", [
    (dict(layer_mixers=("attention", "conv")), "layer_mixers"),
    (dict(ssm_heads=0), "layer_mixers"),
    (dict(ssm_conv=1), "layer_mixers"),
    (dict(layer_mixers=()), "layer_mixers"),        # sizes without ssm
    (dict(residual_scale=0.0), "residual_scale"),
    (dict(logit_divisor=-8.0), "logit_divisor"),
    (dict(attention="paged", kv_heads=0, attn_scale=0.5), "attn_scale"),
    (dict(positions="nope"), "is not one of"),
])
def test_layer_spec_validate_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        _spec(**change).validate()


@pytest.mark.parametrize("change,match", [
    (dict(ffn="gelu_mlp"), "a hybrid of state layers and grouped"),
    (dict(norm="layernorm"), "a hybrid of state layers and grouped"),
    (dict(layer_windows=(0, 8, 0)), "a hybrid of state layers and grouped"),
    (dict(positions="per_layer", layer_positions=("none",) * 3,
          residual="parallel", ffn="routed_experts", top_k=2),
     "mixer\\) and the stream's scalars"),
    (dict(positions="learned", attention="paged", kv_heads=0, attn_scale=0,
          layer_mixers=(), ssm_heads=0, ssm_head_dim=0, ssm_state=0,
          ssm_conv=0, ssm_chunk=0), "the stream's scalars"),
])
def test_serving_refuses_blocks_it_has_not_built(change, match):
    with pytest.raises(NotImplementedError, match=match):
        serving_layers.check_spec(_spec(**change))


@pytest.mark.parametrize("scalar,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", DH ** -0.5), ("logits_scaling", 1.0)])
def test_each_scalar_reaches_the_served_logits(scalar, value):
    """The engine's first logits follow the reference under the
    published scalar and under another: the spec carries all four."""
    model, params = _model(**{scalar: value})
    got, _ = _drive(model, params, _prompt(9, 7), 1, CHUNK)
    want = _ref_logits(model, params, _prompt(9, 7))[-1]
    assert np.abs(got[0] - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    base = _ref_logits(*_model(), _prompt(9, 7))[-1]
    assert np.abs(want - base).max() > 1e-3 * np.abs(base).max()


def test_families_without_a_state_lower_as_before():
    """A spec with no mixers and unit scalars takes none of the new
    branches: `_scaled` hands its argument back, and the grouped block
    is asked for the default scale."""
    x = jnp.ones((2, 3))
    assert serving_layers._scaled(x, 1.0) is x
    spec = LayerSpec(norm="layernorm", positions="learned", attention="paged",
                     ffn="gelu_mlp", head="tied", eps=1e-5).validate()
    assert not spec.has_state and spec.state_layers(4) == ()
    assert spec.mixer_of(3) == "attention" and not spec.rotates(0)
    assert (spec.attn_scale or None) is None
