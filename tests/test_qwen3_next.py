"""Qwen3-Next through the serving engine: gated delta-rule mixers that keep
a matrix state a slot beside gated grouped-attention layers with partial
rotary positions, softmax-routed experts beside a gated shared expert —
against the plain reference (`benchmarks/reference/qwen3_next.py`, the
recurrence only) on seeded weights at toy widths: 8 layers (two periods
of delta, delta, delta, full), hidden 32, 4 query heads on 2 K/V heads of
16 (8 of them rotate), 2 key heads and 4 value heads of 8, convolution 4,
scan chunk 4, 16 experts top 4 of width 16, vocabulary 97.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import qwen3_next as ref
from deepspeed_tpu.models import LayerSpec
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (PagedKVCache, ServeConfig, ServeEngine,
                                   ServeProgramBuilder, ServeSchedule)
from deepspeed_tpu.serving import layers as serving_layers
from deepspeed_tpu.serving.kv_cache import cache_plan

VOCAB, LAYERS, PERIOD = 97, 8, 4
HEADS, KV, DH, ROT = 4, 2, 16, 8
HK, HV, DK, DV, TAPS, SCAN = 2, 4, 8, 8, 4, 4
EXPERTS, TOPK, FF = 16, 4, 16
BS, CHUNK, SEQ = 4, 8, 64
DELTA_LAYERS = (0, 1, 2, 4, 5, 6)
CONV = 2 * HK * DK + HV * DV


def _config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=SEQ, num_layers=LAYERS,
                period=PERIOD, d_model=32, num_heads=HEADS, kv_heads=KV,
                head_dim=DH, rotary_dim=ROT, rope_theta=1e4,
                gdn_key_heads=HK, gdn_value_heads=HV, gdn_key_dim=DK,
                gdn_value_dim=DV, gdn_conv=TAPS, gdn_chunk=SCAN,
                d_expert=FF, d_shared=FF, num_experts=EXPERTS, top_k=TOPK,
                init_std=0.2, init_dt=(1e-3, 0.5))
    base.update(kw)
    return Qwen3NextConfig(**base)


def _kw(cfg):
    return dict(attends=tuple(cfg.attends(i) for i in range(cfg.num_layers)),
                heads=cfg.num_heads, kv_heads=cfg.kv_heads,
                rotary=cfg.rotary_dim, theta=cfg.rope_theta,
                key_heads=cfg.gdn_key_heads, value_heads=cfg.gdn_value_heads,
                key_dim=cfg.gdn_key_dim, top_k=cfg.top_k,
                first_expert=cfg.first_expert, eps=cfg.rms_norm_eps)


def _serve(**kw):
    base = dict(block_size=BS, num_blocks=64, max_batch=3,
                prefill_chunk=CHUNK, max_seq_len=SEQ, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


_MODELS = {}


def _model(dtype=jnp.float32, **kw):
    key = (jnp.dtype(dtype).name, repr(sorted(kw.items())))
    if key not in _MODELS:
        model = Qwen3Next(_config(param_dtype=dtype, **kw))
        _MODELS[key] = model, jax.jit(model.init)(jax.random.PRNGKey(0))
    return _MODELS[key]


_BUILT = {}


def _engine(model, params, **kw):
    from deepspeed_tpu.kernels import get_kernel_config

    serve = _serve(**kw)
    key = (repr(model.config), repr(serve), repr(get_kernel_config()))
    eng = ServeEngine(model, params, serve, programs=_BUILT.get(key))
    _BUILT[key] = eng.programs
    return eng


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


def _ref_logits(model, params, tokens):
    """The reference's logits at every position of `tokens`, at the one
    width `SEQ` (causal: what stands behind a position does not reach
    it), so the reference compiles once a configuration."""
    padded = np.zeros((1, SEQ), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(ref.logits(params, jnp.asarray(padded),
                                 **_kw(model.config)))[0, :len(tokens)]


# the logits have a standard deviation of ~1.2.  float32: the largest
# difference, under a thousandth of that — the chunked form sums a chunk
# in another order than the recurrence and inverts a triangle, through
# eight layers whose norms divide by a root mean square, and a routing
# weight is a quotient of softmax scores.  bf16: the mean difference —
# the inputs of every product rounded to 8 bits of mantissa, on weights
# drawn at a quarter of the float32 tests' scale (logits of standard
# deviation 0.3): at the toy's keys of 8 values a state of more tokens
# than that is read through directions the query hardly has, and a bf16
# model at the float32 tests' scale differs by a fifth of a standard
# deviation where one layer at the published 128 differs by 0.3 %
TOL = {"float32": 1e-3, "bfloat16": 1e-2}
BF16 = {"bfloat16": dict(init_std=0.05)}


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
    model, params = _model(jnp.dtype(dtype), **BF16.get(dtype, {}))
    tokens = _prompt(length, length)
    got = model.apply(params, jnp.asarray([tokens]))[0]
    want = _ref_logits(model, params, tokens)
    assert want.std() > (0.3 if dtype == "float32" else 0.2)
    assert _differ(got, want, dtype) < TOL[dtype]


def test_reference_is_independent_of_the_model_under_test():
    import inspect

    src = inspect.getsource(ref)
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    assert "cumsum" not in src and "lax.scan" in src  # recurrence only
    assert 'HIGHEST = "highest"' in src


def test_the_published_pattern_and_sizes():
    model = Qwen3Next(Qwen3NextConfig(vocab_size=18992, num_layers=12,
                                      experts_held=64))
    spec = model.layer_spec()
    assert [i for i in range(12) if spec.mixer_of(i) == "attention"] == \
        [3, 7, 11]
    assert [spec.rotates(i) for i in range(4)] == [False] * 3 + [True]
    assert spec.state_layers(12) == (0, 1, 2, 4, 5, 6, 8, 9, 10)
    assert spec.has_state and spec.gdn_conv_width == 8192
    assert spec.state_shapes == (((32, 128, 128), "float32"),
                                 ((3, 8192), None))
    assert spec.state_chunk == 64 and spec.held == (0, 64)
    assert (spec.attn_gate, spec.qk_norm, spec.rotary_dim,
            spec.rope_halves, spec.shared) == (True, True, 64, True, "gated")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert model.num_params(shapes) == 2_929_374_400
    assert "gdn" in shapes["blocks"][2] and "attn" in shapes["blocks"][3]


def test_seeded_heads_remember_from_a_few_tokens_to_beyond_the_prompt():
    """A head forgets over 1 / (A step) tokens: at the published draw's
    ranges from ~3 tokens to ~500,000, a fifth of them and more beyond
    the cell's longest prompt — a state lost at a chunk boundary then
    shows in the logits, which a careless draw would hide."""
    model = Qwen3Next(Qwen3NextConfig(vocab_size=64, num_layers=4,
                                      d_model=64, experts_held=8))
    memory = []
    for seed in range(4):
        p = jax.jit(model.init)(jax.random.PRNGKey(seed))["blocks"][0]["gdn"]
        step = np.asarray(jax.nn.softplus(p["dt_bias"]))
        assert (1.9e-6 <= step).all() and (step <= 2.1e-2).all()
        memory += list(1.0 / (step * np.exp(np.asarray(p["A_log"]))))
    memory = np.asarray(memory)
    assert memory.min() < 50 and (memory > 12288).mean() > 0.2
    taps = np.asarray(p["conv_w"], np.float32)
    assert np.abs(taps).max() <= 0.5 and taps.std() > 0.25


# -- the two forms of the rule --------------------------------------------------


def _rule_inputs(T, seed=0, B=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(k[0], (B, T, HV, DK))) * DK ** -0.5
    kk = unit(jax.random.normal(k[1], (B, T, HV, DK)))
    v = jax.random.normal(k[2], (B, T, HV, DV))
    g = -jax.random.uniform(k[3], (B, T, HV), minval=1e-3, maxval=1.5)
    beta = jax.random.uniform(k[4], (B, T, HV), minval=0.05, maxval=0.95)
    state = jax.random.normal(k[5], (B, HV, DK, DV))
    return q, kk, v, g, beta, state


def _by_steps(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = qn.delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                 beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("T,chunk", [(4, 4), (8, 4), (12, 2), (6, 6),
                                     (16, 1), (64, 64), (128, 64)])
def test_the_chunked_form_is_the_recurrence(T, chunk):
    """From a state that is not zero: outputs and the state left behind,
    one chunk and several, to float32 rounding — at the cell's chunk of
    64 too, where the triangle inverted is 64 x 64."""
    args = _rule_inputs(T)
    o, state = jax.jit(qn.delta_scan, static_argnums=6)(*args, chunk)
    want_o, want_state = _by_steps(*args)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)


def test_the_rule_is_the_delta_rule():
    """What `delta_step` writes: after the step the state answers k^
    with (1 - beta) of what it answered before (decayed) plus beta v —
    the delta rule — and with beta = 0 and g = 0 nothing moves."""
    q, k, v, g, beta, state = _rule_inputs(1)
    q, k, v, g, beta = q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
    _, new = qn.delta_step(q, k, v, g, beta, state)
    read = lambda s: jnp.einsum("bhkv,bhk->bhv", s, k)
    before = read(state * jnp.exp(g)[..., None, None])
    np.testing.assert_allclose(
        read(new), (1 - beta[..., None]) * before + beta[..., None] * v,
        atol=1e-5)
    _, same = qn.delta_step(q, k, v, 0 * g, 0 * beta, state)
    np.testing.assert_array_equal(same, state)


@pytest.mark.parametrize("size", [1, 2, 3, 6, 64])
def test_the_triangle_is_inverted_by_blocks(size):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(size),
                                   (3, 2, size, size)) * 0.3, -1)
    want = np.linalg.inv(np.eye(size) + np.asarray(a, np.float64))
    np.testing.assert_allclose(jax.jit(qn._solve_unit_lower)(a), want,
                               atol=1e-5)


def _mix(model, params, h, state, conv, n_valid):
    return qn.gdn_mix(model.layer_spec(), params["blocks"][0]["gdn"], h,
                      state, conv, jnp.asarray(n_valid, jnp.int32))


@pytest.mark.parametrize("n_valid", [0, 1, 2, 3, 5, 8])
def test_a_padded_tail_moves_neither_state_nor_taps(n_valid):
    """A chunk of 8 with `n_valid` real positions leaves what the valid
    prefix alone leaves — the state, and the convolution's last three
    VALID inputs, also where the chunk has fewer than three — whatever
    stands in the padding."""
    model, params = _model()
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    h = jax.random.normal(k[0], (1, 8, 32))
    state = jax.random.normal(k[1], (1, HV, DK, DV))
    conv = jax.random.normal(k[2], (1, TAPS - 1, CONV))
    out, s1, c1 = _mix(model, params, h, state, conv, [n_valid])
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
    np.testing.assert_allclose(c1, c, atol=1e-6)
    if n_valid == 0:
        np.testing.assert_array_equal(s1, state)
        np.testing.assert_array_equal(c1, conv)


def test_a_decode_step_has_no_term_across_slots():
    """Three slots, the middle one not running: its state and its
    convolution inputs come back bit for bit, and the running slots'
    outputs and states do not depend on what the others hold."""
    model, params = _model()
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    h = jax.random.normal(k[0], (3, 1, 32))
    state = jax.random.normal(k[1], (3, HV, DK, DV))
    conv = jax.random.normal(k[2], (3, TAPS - 1, CONV))
    out, s1, c1 = _mix(model, params, h, state, conv, [1, 0, 1])
    np.testing.assert_array_equal(s1[1], state[1])
    np.testing.assert_array_equal(c1[1], conv[1])
    assert not np.array_equal(s1[0], state[0])
    h2 = h.at[1].set(jax.random.normal(k[3], (1, 32)))
    state2 = state.at[1].set(jax.random.normal(k[4], (HV, DK, DV)))
    out2, s2, c2 = _mix(model, params, h2, state2, conv * jnp.asarray(
        [1.0, -3.0, 1.0])[:, None, None], [1, 1, 1])
    for slot in (0, 2):
        np.testing.assert_array_equal(out[slot], out2[slot])
        np.testing.assert_array_equal(s1[slot], s2[slot])
        np.testing.assert_array_equal(c1[slot], c2[slot])


# -- the rule over the live slots (kernels/gdn.py) -------------------------------


def _forced():
    """The registry's own override: the `gdn_step` kernel, under the
    Pallas interpreter."""
    from deepspeed_tpu.kernels import kernel_config

    return kernel_config(ops={"gdn_step": "pallas"}, interpret=True)


@pytest.mark.parametrize("live", [
    (0, 0, 0, 0, 0, 0), (0, 0, 0, 1, 0, 0), (1, 1, 1, 1, 1, 1),
    (1, 0, 1, 1, 0, 1), (0, 0, 0, 0, 0, 1)],
    ids=["none", "one", "all", "scattered", "last"])
@pytest.mark.parametrize("heads,d,tiles", [(8, 128, 1), (16, 128, 2),
                                           (HV, DK, 1)])
def test_the_kernel_steps_the_live_slots_and_no_others(live, heads, d, tiles,
                                                       monkeypatch):
    """Against `delta_step` under g = 0 and beta = 0 for a slot that is
    not live: the live slots' state and o to float32 tolerance, every
    other slot's state the input's bit for bit and its o zeros — a
    slot's state as one block and as two tiles of heads, at the published
    head's 128 x 128 and at the toy's."""
    from deepspeed_tpu.kernels import gdn, registry, ssm

    if tiles > 1:
        monkeypatch.setattr(ssm, "_STATE_BLOCK_BYTES",
                            4 * (heads // tiles) * d * d * 4)
    assert ssm.head_tile(heads, d, d) == heads // tiles
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    on = np.asarray(live, bool)
    mask = jnp.asarray(on, jnp.float32)[:, None]
    q = unit(jax.random.normal(k[0], (6, heads, d))) * d ** -0.5
    kk = unit(jax.random.normal(k[1], (6, heads, d)))
    v = jax.random.normal(k[2], (6, heads, d))
    g = -jax.random.uniform(k[3], (6, heads), maxval=1.0) * mask
    beta = jax.random.uniform(k[4], (6, heads)) * mask
    state = jax.random.normal(k[5], (6, heads, d, d))
    ids, n = ssm.live_slots(jnp.asarray(live))
    assert int(n) == on.sum()
    want_o, want_state = qn.delta_step(q, kk, v, g, beta, state)
    with _forced():
        o, got = jax.jit(lambda *a: registry.dispatch(
            "gdn_step", *a, info=gdn.gdn_step_info(state)))(
                q, kk, v, g, beta, state, ids, n)
    o, got = np.asarray(o), np.asarray(got)
    np.testing.assert_array_equal(got[~on], np.asarray(state)[~on])
    np.testing.assert_array_equal(o[~on], 0.0)
    np.testing.assert_allclose(got[on], np.asarray(want_state)[on],
                               atol=2e-6, rtol=1e-6)
    np.testing.assert_allclose(o[on], np.asarray(want_o)[on], atol=1e-5,
                               rtol=1e-5)


def test_the_kernel_is_chosen_by_what_the_call_shows():
    """Off a TPU, and for a state that is not whole float32 tiles, the
    rule is `delta_step` itself; forced, the registry says why."""
    from deepspeed_tpu.kernels import gdn, registry

    cell = gdn.gdn_step_info(jax.ShapeDtypeStruct((48, 32, 128, 128),
                                                 jnp.float32))
    toy = gdn.gdn_step_info(jax.ShapeDtypeStruct((3, HV, DK, DV),
                                                jnp.float32))
    op = registry.get_kernel("gdn_step")
    assert op.auto_supports("default", cell) == (True, "")
    assert not op.auto_supports("default", toy)[0]
    assert registry.resolve_impl("gdn_step", info=cell) == "jnp"   # the CPU
    with pytest.raises(RuntimeError, match="backend is 'cpu'"):
        registry.resolve_impl("gdn_step", impl="pallas", info=cell)


# -- gated attention --------------------------------------------------------------


def test_rotary_turns_the_first_values_of_a_head_in_halves():
    """Dims i and i + rotary / 2 of the first `rotary` values turn by
    p theta^(-2i/rotary); the rest of the head passes."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 2, DH))
    pos = jnp.arange(5)[None] + 3
    got = np.asarray(qn.rope_partial(x, pos, 1e4, ROT, True))
    np.testing.assert_array_equal(got[..., ROT:], np.asarray(x)[..., ROT:])
    i = np.arange(ROT // 2)
    ang = np.asarray(pos)[0][:, None] * 1e4 ** (-2.0 * i / ROT)     # [5, 4]
    a, b = np.asarray(x)[0, :, :, :ROT // 2], np.asarray(x)[0, :, :, ROT // 2:ROT]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    np.testing.assert_allclose(got[0, :, :, :ROT // 2], a * cos - b * sin,
                               atol=1e-5)
    np.testing.assert_allclose(got[0, :, :, ROT // 2:ROT], b * cos + a * sin,
                               atol=1e-5)
    # the whole head, and the other pairing, through the same function
    whole = qn.rope_partial(x, pos, 1e4, 0, True)
    assert not np.allclose(whole[..., ROT:], x[..., ROT:])
    from deepspeed_tpu.models.cohere2_moe import rope_interleaved
    np.testing.assert_array_equal(qn.rope_partial(x, pos, 1e4, 0, False),
                                  rope_interleaved(x, pos, 1e4))


@pytest.mark.parametrize("field,value", [
    ("attn_gate", False), ("qk_norm", False), ("rotary_dim", 0),
    ("rope_halves", False)])
def test_each_attention_field_reaches_the_projection(field, value):
    """`project_gated` reads the spec: each of the three additions (and
    the pairing) changes q, k or the gate, and off is what
    `project_grouped` gives."""
    from deepspeed_tpu.models.cohere2_moe import project_grouped

    model, params = _model()
    cfg, spec = model.config, model.layer_spec()
    p = dict(params["blocks"][3]["attn"])
    p["q_norm"] = {"scale": jnp.full((DH,), 0.5)}
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 6, 32))
    pos = jnp.arange(6)[None] + 2
    base = qn.project_gated(cfg, spec, p, h, pos, True, jnp.float32)
    other = qn.project_gated(cfg, spec._replace(**{field: value}),
                             dict(p, q=p["q"][:, :HEADS * DH])
                             if field == "attn_gate" else p,
                             h, pos, True, jnp.float32)
    if field == "attn_gate":
        assert base[3].shape == (1, 6, HEADS * DH) and other[3] is None
    else:
        assert not np.allclose(base[0], other[0])
    plain = spec._replace(attn_gate=False, qk_norm=False, rotary_dim=0,
                          rope_halves=False)
    q, k, v, gate = qn.project_gated(
        cfg, plain, dict(p, q=p["q"][:, :HEADS * DH]), h, pos, True,
        jnp.float32)
    want = project_grouped(cfg, dict(p, q=p["q"][:, :HEADS * DH]), h, pos,
                           True, jnp.float32)
    assert gate is None
    for a, b in zip((q, k, v), want):
        np.testing.assert_array_equal(a, b)


# -- the shares add up ------------------------------------------------------------


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """One layer's FFN: the routed parts that eight chips of 2 experts
    each give, plus the gated shared expert counted once, are what the
    uncut reference gives for the whole layer — and the shared expert's
    gate is in it."""
    from deepspeed_tpu.models.cohere2_moe import routed_ffn

    whole, params = _model()
    mlp = params["blocks"][0]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(6), (11, 32))
    with jax.default_matmul_precision("highest"):
        want = ref._moe(h, mlp, top_k=TOPK, first_expert=0)
        sh = mlp["shared"]
        shared = jax.nn.sigmoid(h @ mlp["shared_gate"]) * ref._gated(
            h, sh["gate"], sh["up"], sh["down"])
    total = jnp.zeros_like(h)
    for chip in range(8):
        cfg = _config(experts_held=2, first_expert=2 * chip)
        spec = Qwen3Next(cfg).layer_spec()
        part = dict(mlp, experts=jax.tree_util.tree_map(
            lambda a: a[2 * chip:2 * chip + 2], mlp["experts"]))
        y, idx, count, held = routed_ffn(spec, cfg, part, h)
        assert count == 2 and held.shape == idx.shape == (11, TOPK)
        # what the reference gives the same share
        with jax.default_matmul_precision("highest"):
            alone = ref._moe(h, part, top_k=TOPK, first_expert=2 * chip)
        np.testing.assert_allclose(y, alone, atol=2e-5)
        total = total + (y - shared)
    np.testing.assert_allclose(total + shared, want, atol=1e-4)
    ungated = ref._gated(h, sh["gate"], sh["up"], sh["down"])
    assert np.abs(np.asarray(shared - ungated)).max() > 0.01


# -- through the programs ---------------------------------------------------------


_PROGRAMS = {}


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
    assert plan.table_width == W
    kv = PagedKVCache(plan, 64, prefix_cache=False)
    key = (repr(model.config), sched)
    if key not in _PROGRAMS:
        builder = ServeProgramBuilder(model, sched)
        _PROGRAMS[key] = builder.build(), jax.jit(builder.step_logits)
    progs, step = _PROGRAMS[key]
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
    tok = int(tok[0])       # behind routed FFNs [sample, rows multiplied]
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
@pytest.mark.parametrize("held", [(0, 0), (8, 4)], ids=["all", "share"])
def test_prefill_in_chunks_then_decode_is_the_reference_forward(length,
                                                                chunk, held):
    """A prompt through chunks that do and do not divide it, shorter
    than the convolution's three kept inputs among them, then five
    decode steps through the cache: every logits row is the reference's
    full forward's to 1e-3 (logits of standard deviation 1.2; float32),
    so the state and the taps crossed every boundary — with every expert
    held, and with experts 4-11 of 16."""
    model, params = _model(experts_held=held[0], first_expert=held[1])
    prompt = _prompt(length, length)
    got, _ = _drive(model, params, prompt, 5, chunk)
    tokens = list(prompt)
    for i in range(6):
        want = _ref_logits(model, params, tokens)[-1]
        assert np.abs(got[i] - want).max() < TOL["float32"], (i, length)
        tokens.append(int(np.argmax(got[i])))


def test_the_state_left_behind_does_not_depend_on_the_chunking():
    """The same prompt through chunks of 8, 4 and 1: the slot's state
    agrees to float32 rounding, the taps too, and the other slots'
    entries are still zero."""
    model, params = _model()
    prompt = _prompt(13, 5)
    _, a = _drive(model, params, prompt, 0, 8)
    for chunk in (4, 1):
        _, b = _drive(model, params, prompt, 0, chunk)
        for i in DELTA_LAYERS:
            np.testing.assert_allclose(a[i][0][1], b[i][0][1], atol=2e-5)
            np.testing.assert_allclose(a[i][1][1], b[i][1][1], atol=2e-5)
    for i in DELTA_LAYERS:
        assert np.abs(a[i][0][1]).max() > 0
        for other in (0, 2):
            assert not np.asarray(a[i][0][other]).any()
            assert not np.asarray(a[i][1][other]).any()


def test_a_prefill_chunk_must_be_whole_scan_chunks():
    model, _ = _model()
    sched = ServeSchedule(max_batch=2, prefill_chunk=6, block_size=BS,
                          num_blocks=64, table_width=SEQ // BS)
    with pytest.raises(ValueError, match=r"scan chunk \(4\)"):
        ServeProgramBuilder(model, sched)


def _alone(model, params, prompt, n, **kw):
    return _engine(model, params, **kw).generate([prompt], n)[0]


@pytest.mark.parametrize("way", ["oracle", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_engine_matches_the_reference_forward(dtype, way):
    """Four requests through `ServeEngine` (chunks of 8, three slots, one
    waiting for a slot): each greedy token is the reference's own argmax,
    or, in bf16 (logits of standard deviation 0.3), within 0.05 of the
    reference's largest logit."""
    import contextlib

    model, params = _model(jnp.dtype(dtype), **BF16.get(dtype, {}))
    prompts = [_prompt(n, 30 + i) for i, n in enumerate((19, 5, 11, 9))]
    with _forced() if way == "kernel" else contextlib.nullcontext():
        outs = _engine(model, params).generate(prompts, 10)
    for prompt, out in zip(prompts, outs):
        lg = _ref_logits(model, params, prompt + out)
        lg = lg[len(prompt) - 1:len(prompt) + len(out) - 1]
        gap = lg.max(-1) - lg[np.arange(len(out)), out]
        assert gap.max() <= (0.0 if dtype == "float32" else 0.05), gap


def test_a_request_does_not_depend_on_its_neighbours():
    model, params = _model()
    prompts = [_prompt(n, 40 + i) for i, n in enumerate((17, 3, 9))]
    together = _engine(model, params).generate(prompts, 8)
    for prompt, out in zip(prompts, together):
        assert _alone(model, params, prompt, 8) == out


@pytest.mark.parametrize("way", ["oracle", "kernel"])
def test_a_seated_slot_starts_from_zeros(way, monkeypatch):
    """One slot, four requests one after another, each ending on an
    `eos_token` the loop finds a step late — the retired slot is stepped
    once more — and the next request is seated in it at once: each
    answer is the one the request gets in a fresh engine.  Without the
    zeroing at seating it is not."""
    import contextlib

    model, params = _model()
    prompts = [_prompt(n, 20 + i) for i, n in enumerate((9, 2, 13, 5))]
    with _forced() if way == "kernel" else contextlib.nullcontext():
        full = [_alone(model, params, p, 6, max_batch=1) for p in prompts]
        eos = [out[2] for out in full]
        want = [out[:out.index(e) + 1] for out, e in zip(full, eos)]

        def serve_all():
            eng = _engine(model, params, max_batch=1)
            before = COUNTERS.snapshot()
            reqs = [eng.submit(p, 6, eos_token=e)
                    for p, e in zip(prompts, eos)]
            eng.run()
            return [r.out for r in reqs], COUNTERS.delta_since(before)

        outs, d = serve_all()
        assert outs == want
        assert d["serve.gdn.state_resets"]["calls"] == 4
        assert "serve.ssm.state_resets" not in d
        monkeypatch.setattr(PagedKVCache, "reset_state",
                            lambda self, slot: None)
        assert serve_all()[0] != want


def test_the_cache_holds_a_matrix_state_a_slot_beside_rows():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    kv = eng.kv
    for i, entry in enumerate(kv.caches):
        if i in DELTA_LAYERS:
            assert [a.shape for a in entry] == [(3, HV, DK, DV),
                                                (3, TAPS - 1, CONV)]
            assert entry[0].dtype == jnp.float32
        else:
            assert [a.shape for a in entry] == [(64 * BS, 128)] * 2
    state = 6 * 3 * (HV * DK * DV * 4 + (TAPS - 1) * CONV * 4)
    assert kv.state_nbytes() == state
    assert kv.bytes_per_block() == 2 * 2 * BS * 128 * 4   # two layers' rows
    assert "6 layer(s) with no rows and a state a slot, 3 slots" in \
        kv.describe()


def test_counters_of_the_delta_layers():
    """`serve.gdn.*` name for name with `serve.ssm.*`, which this family
    does not emit; `serve.moe.*` and `serve.attn.*` as the shared code
    emits them."""
    model, params = _model(experts_held=8, first_expert=4)
    eng = _engine(model, params, max_batch=4)
    before = COUNTERS.snapshot()
    eng.generate([_prompt(9, 1), _prompt(18, 2)], 6)
    d = COUNTERS.delta_since(before)
    assert not [k for k in d if k.startswith("serve.ssm.")]
    assert d["serve.gdn.state_resets"] == {"calls": 2, "bytes": 0}
    # chunks of 8: 9 -> 8 + 1, 18 -> 8 + 8 + 2
    assert d["serve.prefill_chunks"] == {"calls": 5, "bytes": 27}
    steps = d["serve.decode_steps"]["calls"]
    a_slot = HV * DK * DV * 4 + (TAPS - 1) * CONV * 4
    # the oracle steps all four slots' state in six layers, in and out
    assert d["serve.gdn.state_bytes"] == {
        "calls": steps, "bytes": steps * 2 * 4 * 6 * a_slot}
    assert d["serve.gdn.slots_live"] == {
        "calls": steps, "bytes": d["serve.decode_steps"]["bytes"] * 6}
    # 8 routed layers a step; experts touched among the 8 held
    touched = d["serve.moe.experts_touched"]
    assert touched["calls"] == 8 * steps
    assert 0 < touched["bytes"] <= 8 * touched["calls"]
    assert "serve.moe.assignments" not in d
    # the two full layers' rows, every cached position of each
    rows = d["serve.attn.rows_read"]
    assert rows["calls"] == d["serve.decode_steps"]["bytes"]
    assert rows["bytes"] >= 2 * 10 * rows["calls"]


def test_the_scopes_name_the_mixers_in_both_programs():
    """`gdn.scan` in prefill, `gdn.step` in decode, `gated_attend` in
    both: what a device trace shows of the new layers."""
    model, params = _model()
    sched = ServeSchedule(max_batch=2, prefill_chunk=8, block_size=BS,
                          num_blocks=64, table_width=SEQ // BS)
    builder = ServeProgramBuilder(model, sched)
    kv = PagedKVCache(cache_plan(builder.spec, model.config,
                                 _serve(max_batch=2)), 64,
                      prefix_cache=False)
    step = jax.jit(builder.step_logits).lower(
        params, kv.caches, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
        jnp.zeros((2, SEQ // BS), jnp.int32)).as_text(debug_info=True)
    assert "gdn.step" in step and "gated_attend" in step
    assert "gdn.scan" not in step and "ssm." not in step
    progs = builder.build()
    chunk = progs["prefill"].lower(
        params, kv.caches, jnp.zeros((1, 8), jnp.int32), np.int32(0),
        np.int32(8), jnp.zeros((SEQ // BS + 1,), jnp.int32), np.float32(0),
        np.int32(0), np.uint32(0)).as_text(debug_info=True)
    assert "gdn.scan" in chunk and "gated_attend" in chunk
    assert "moe_shared" in chunk and "moe_route" in chunk


# -- refused, by name ----------------------------------------------------------


@pytest.mark.parametrize("serve,match", [
    (dict(prefix_cache=True), "prefix_cache=True over layers with a state"),
    (dict(draft_len=2), "draft_len > 0 over layers with a state"),
    (dict(kv_dtype="int8"), "kv_dtype 'int8' over layers with a state"),
    (dict(quantized_weights="int8"),
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


def _spec(**kw):
    return _model()[0].layer_spec()._replace(**kw)


@pytest.mark.parametrize("change,match", [
    (dict(layer_mixers=("gdn", "conv", "gdn", "attention")),
     "layer_mixers"),
    (dict(gdn_key_heads=0), "gdn_key_heads"),
    (dict(gdn_value_heads=3), "a multiple of"),
    (dict(gdn_conv=1), "gdn_conv >= 2"),
    (dict(gdn_chunk=0), "gdn_chunk"),
    (dict(layer_mixers=("attention",) * 4), "a pattern with gdn layers"),
    (dict(layer_mixers=("gdn", "ssm", "gdn", "attention"), ssm_heads=2,
          ssm_head_dim=4, ssm_state=4, ssm_conv=2, ssm_chunk=4),
     "of one kind"),
    (dict(rotary_dim=7), "an even rotary_dim"),
    (dict(attention="paged", kv_heads=0, layer_mixers=(), gdn_key_heads=0,
          gdn_value_heads=0, gdn_key_dim=0, gdn_value_dim=0, gdn_conv=0,
          gdn_chunk=0), "describe grouped attention"),
    (dict(shared="weighted"), "is not one of"),
    (dict(ffn="silu_gated", top_k=0, renormalize=False, shared="gated"),
     "describe a\n?\\s*routed_experts FFN|routed_experts FFN"),
])
def test_layer_spec_validate_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        _spec(**change).validate()


@pytest.mark.parametrize("change,match", [
    (dict(ffn="gelu_mlp", top_k=0, renormalize=False, shared="sum"),
     "a hybrid of state layers and grouped attention"),
    (dict(norm="layernorm"), "a hybrid of state layers"),
    (dict(layer_windows=(0, 0, 0, 8)), "a hybrid of state layers"),
    (dict(residual="parallel"), "layers with a state"),
    (dict(shared="average"), "weighs them by a sigmoid gate"),
    (dict(layer_positions=("rope", "none", "none", "rope")),
     "a layer that keeps a state has no positions"),
    # a sequential block with positions layer by layer and no state
    (dict(layer_mixers=(), gdn_key_heads=0, gdn_value_heads=0,
          gdn_key_dim=0, gdn_value_dim=0, gdn_conv=0, gdn_chunk=0),
     "grouped attention elsewhere only in a sequential block"),
    (dict(positions="rope", layer_positions=()), "no block with 'rope'"),
])
def test_serving_refuses_blocks_it_has_not_built(change, match):
    with pytest.raises(NotImplementedError, match=match):
        serving_layers.check_spec(_spec(**change))


def test_the_parallel_block_refuses_the_sequential_blocks_additions():
    from deepspeed_tpu.models import Cohere2Moe, Cohere2MoeConfig

    spec = Cohere2Moe(Cohere2MoeConfig(
        vocab_size=64, num_layers=4, num_heads=4, kv_heads=2, head_dim=8,
        d_model=32, d_expert=16, num_experts=4, top_k=2,
        num_shared=1)).layer_spec()
    serving_layers.check_spec(spec)
    with pytest.raises(NotImplementedError, match="sigmoid gate"):
        serving_layers.check_spec(spec._replace(shared="gated"))
    with pytest.raises(NotImplementedError,
                       match="built in the sequential block"):
        serving_layers.check_spec(spec._replace(attn_gate=True))


def test_families_without_the_new_fields_lower_as_before():
    """A spec that sets none of the new fields takes none of the new
    branches: the defaults are what Command A+ and Granite had."""
    spec = LayerSpec(norm="layernorm", positions="learned", attention="paged",
                     ffn="gelu_mlp", head="tied", eps=1e-5).validate()
    assert (spec.attn_gate, spec.qk_norm, spec.rotary_dim, spec.rope_halves,
            spec.shared) == (False, False, 0, False, "sum")
    assert spec.state_shapes == () and spec.state_chunk == 0
    assert not spec.has_state and spec.state_layers(4) == ()
    from deepspeed_tpu.models import GraniteHybrid, GraniteHybridConfig

    granite = GraniteHybrid(GraniteHybridConfig()).layer_spec()
    assert granite.state_shapes == (((64, 64, 128), "float32"),
                                    ((3, 4352), None))
    assert granite.state_chunk == 256


@pytest.mark.parametrize("module", [
    "deepspeed_tpu", "deepspeed_tpu.serving", "deepspeed_tpu.models",
    "deepspeed_tpu.kernels.registry"])
def test_the_family_is_imported_only_when_it_is_built(module):
    """Nothing of this family at import of the package, of serving, of
    the model zoo or of the kernel registry: another cell's set-up pays
    nothing for it."""
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "bad = [m for m in sys.modules if 'qwen3_next' in m "
            "or m.endswith('kernels.gdn')]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})


@pytest.mark.parametrize("heads,kv,dh", [(16, 2, 256), (8, 2, 128),
                                         (4, 2, 16), (16, 4, 128)])
def test_own_lanes_of_two_kv_heads_keep_what_the_slices_keep(heads, kv, dh):
    """`kernels/paged.py`: the walk keeps the lanes of rows of TWO K/V
    heads under a mask and a sum (`_own_lanes_of_two`: the chip's compiler
    gets the concatenation of exactly two slices wrong, PERF.md section 6,
    PR 57); off the chip both expressions give each score row the lanes
    of its own K/V head, bit for bit, as the slices do for any other
    number of heads."""
    from deepspeed_tpu.kernels import paged

    G, T = heads // kv, 2
    Hp = paged.score_rows(1, heads)
    out = jax.random.normal(jax.random.PRNGKey(7), (3, T * Hp, kv * dh))
    keep = paged._own_lanes_of_two if kv == 2 else paged._own_lanes
    got = np.asarray(keep(out, T, heads, G, dh))
    if kv == 2:
        np.testing.assert_array_equal(
            got, paged._own_lanes(out, T, heads, G, dh))
    rows = np.asarray(out).reshape(3, T, Hp, kv * dh)
    want = np.stack([rows[:, :, h, (h // G) * dh:(h // G + 1) * dh]
                     for h in range(heads)], axis=2)
    assert got.shape == (3, T, heads, dh)
    np.testing.assert_array_equal(got, want)
