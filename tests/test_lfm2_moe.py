"""LFM2-MoE through the serving engine: gated short-convolution mixers
that keep their last two inputs a slot and nothing else, beside
grouped-attention layers with normed and rotated q and k, and
sigmoid-routed SiLU-gated experts behind two dense layers — against the
plain reference (`benchmarks/reference/lfm2_moe.py`) on seeded weights at
toy widths: 8 layers (conv, conv, attention, conv) x 2, hidden 32, 4
query heads on 2 K/V heads of 16, 3 taps, a dense FFN of 48, 16 experts
top 3 of width 16, vocabulary 97.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import lfm2_moe as ref
from deepspeed_tpu.models import LayerSpec
from deepspeed_tpu.models import lfm2_moe as lfm
from deepspeed_tpu.models.layer_spec import STATE_MIXERS
from deepspeed_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import PagedKVCache, ServeConfig, ServeEngine
from deepspeed_tpu.serving import layers as serving_layers

VOCAB, TYPES = 97, ("conv", "conv", "full_attention", "conv") * 2
D, HEADS, KV, DH, TAPS = 32, 4, 2, 16, 3
EXPERTS, TOPK, FF, DENSE = 16, 3, 16, 2
BS, CHUNK, SEQ = 4, 8, 64
CONV_LAYERS, ROW_LAYERS = (0, 1, 3, 4, 5, 7), (2, 6)


def _config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=SEQ, layer_types=TYPES,
                d_model=D, d_ffn=48, dense_layers=DENSE, num_heads=HEADS,
                kv_heads=KV, head_dim=DH, rope_theta=1e4, conv_taps=TAPS,
                d_expert=FF, num_experts=EXPERTS, top_k=TOPK, init_std=0.2,
                bias_std=0.05)
    base.update(kw)
    return Lfm2MoeConfig(**base)


def _kw(cfg):
    return dict(layer_types=cfg.layer_types, heads=cfg.num_heads,
                kv_heads=cfg.kv_heads, top_k=cfg.top_k,
                first_expert=cfg.first_expert, route_scale=cfg.route_scale,
                theta=cfg.rope_theta, eps=cfg.norm_eps)


def _serve(**kw):
    base = dict(block_size=BS, num_blocks=64, max_batch=3,
                prefill_chunk=CHUNK, max_seq_len=SEQ, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


_MODELS = {}


def _model(dtype=jnp.float32, **kw):
    key = (jnp.dtype(dtype).name, repr(sorted(kw.items())))
    if key not in _MODELS:
        model = Lfm2Moe(_config(param_dtype=dtype, **kw))
        _MODELS[key] = model, jax.jit(model.init)(jax.random.PRNGKey(0))
    return _MODELS[key]


_BUILT = {}


def _engine(model, params, **kw):
    serve = _serve(**kw)
    key = (repr(model.config), repr(serve))
    eng = ServeEngine(model, params, serve, programs=_BUILT.get(key))
    _BUILT[key] = eng.programs
    return eng


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


def _ref_logits(model, params, tokens):
    """The reference's logits at every position of `tokens`, at the one
    width `SEQ` (causal), so the reference compiles once."""
    padded = np.zeros((1, SEQ), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(ref.logits(params, jnp.asarray(padded),
                                 **_kw(model.config)))[0, :len(tokens)]


# the logits have a standard deviation of ~1.1.  float32: the largest
# difference; bf16: the mean difference, on weights at a quarter of the
# float32 tests' scale (every product's inputs at 8 bits of mantissa)
TOL = {"float32": 1e-4, "bfloat16": 1e-2}
BF16 = {"bfloat16": dict(init_std=0.05)}


def _differ(got, want, dtype):
    d = np.abs(np.asarray(got, np.float32) - want)
    return d.max() if dtype == "float32" else d.mean()


# -- the uncached forward against the reference -------------------------------


@pytest.mark.parametrize("length", [1, 2, 3, 9, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_plain_reference(dtype, length):
    """Lengths under the convolution's two kept rows, at them and past
    them."""
    model, params = _model(jnp.dtype(dtype), **BF16.get(dtype, {}))
    tokens = _prompt(length, length)
    got = model.apply(params, jnp.asarray([tokens]))[0]
    want = _ref_logits(model, params, tokens)
    assert want.std() > (0.5 if dtype == "float32" else 0.05)
    assert _differ(got, want, dtype) < TOL[dtype]


def test_reference_is_independent_of_the_model_under_test():
    import inspect

    src = inspect.getsource(ref)
    assert "deepspeed_tpu" not in src.replace(
        "`deepspeed_tpu.models.lfm2_moe\n.Lfm2Moe.init`", "")
    assert "import deepspeed_tpu" not in src and "from deepspeed_tpu" \
        not in src


def test_the_published_pattern_and_what_a_slot_keeps():
    """40 layers: attention where l mod 4 = 2, the gated convolution
    elsewhere; two dense layers then 38 that route; a slot keeps two
    rows of 2,048 in each of 30 layers and rows in 10."""
    cfg = Lfm2MoeConfig()
    spec = Lfm2Moe(cfg).layer_spec()
    n = cfg.num_layers
    assert n == 40
    assert spec.row_layers(n) == tuple(range(2, 40, 4))
    assert spec.state_layers(n) == tuple(i for i in range(40) if i % 4 != 2)
    assert spec.routed_layers(n) == tuple(range(2, 40))
    assert all(spec.rotates(i) == (i % 4 == 2) for i in range(n))
    assert spec.state_mixer == "conv" and spec.has_state
    assert spec.state_shapes == (((2, 2048), None),)
    assert spec.state_chunk == 0
    assert spec.norm == "rmsnorm" and spec.qk_norm and spec.rope_halves
    assert spec.rotary_dim == 0 and spec.head == "tied"
    assert (spec.top_k, spec.scoring, spec.select_bias, spec.renormalize,
            spec.renorm_eps, spec.route_scale) == (
                4, "sigmoid", True, True, 1e-6, 1.0)


# -- the gated short convolution ----------------------------------------------


def _conv_inputs(T, B=2, seed=0):
    model, params = _model()
    p = params["blocks"][0]["conv"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (B, T, D))
    rows = jnp.zeros((B, TAPS - 1, D), jnp.float32)
    return model.layer_spec(), p, h, rows


def _conv_by_hand(p, h):
    """The layer written out position by position, float64."""
    h = np.asarray(h, np.float64)
    w_in, w, w_out = (np.asarray(p[k], np.float64)
                      for k in ("in", "conv_w", "out"))
    b, c, u = np.split(h @ w_in, 3, axis=-1)
    g = b * u
    out = np.zeros_like(g)
    for t in range(h.shape[1]):
        for j in range(TAPS):
            s = t - (TAPS - 1) + j
            if s >= 0:
                out[:, t] += w[:, j] * g[:, s]
    return (c * out) @ w_out, g


def test_conv_mix_is_the_convolution_written_out():
    spec, p, h, rows = _conv_inputs(7)
    out, kept = lfm.conv_mix(spec, p, h, rows, jnp.full((2,), 7))
    want, g = _conv_by_hand(p, h)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(kept, g[:, -2:], rtol=1e-5, atol=1e-6)
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("split", list(range(1, 9)))
def test_conv_mix_a_chunk_at_a_time_is_the_whole_sequence(split):
    """Every split point of a sequence of 9 — the second call starts
    from the rows the first left — gives what one call over the whole
    gives; from zeros instead it does not."""
    spec, p, h, rows = _conv_inputs(9, seed=split)
    whole, kept_whole = lfm.conv_mix(spec, p, h, rows, jnp.full((2,), 9))
    a, kept = lfm.conv_mix(spec, p, h[:, :split], rows,
                           jnp.full((2,), split))
    b, kept = lfm.conv_mix(spec, p, h[:, split:], kept,
                           jnp.full((2,), 9 - split))
    np.testing.assert_allclose(jnp.concatenate([a, b], 1), whole,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(kept, kept_whole, rtol=1e-6, atol=1e-7)
    lost, _ = lfm.conv_mix(spec, p, h[:, split:], rows,
                           jnp.full((2,), 9 - split))
    assert np.abs(np.asarray(lost - b)).max() > 1e-3


@pytest.mark.parametrize("valid", [(0, 0), (1, 5), (2, 8), (8, 3)])
def test_a_padded_chunk_leaves_its_last_two_valid_rows(valid):
    """A chunk of 8 of which `valid` positions are real, a sequence: the
    rows handed back are the last two of [kept | valid inputs] — a
    sequence with none gets its own back, one with one keeps the newer
    of its old two."""
    spec, p, h, _ = _conv_inputs(8, seed=3)
    rows = jax.random.normal(jax.random.PRNGKey(8), (2, TAPS - 1, D))
    _, kept = lfm.conv_mix(spec, p, h, rows, jnp.asarray(valid))
    _, g = _conv_by_hand(p, h)
    for b, n in enumerate(valid):
        want = np.concatenate([np.asarray(rows[b]), g[b, :n]])[-2:]
        np.testing.assert_allclose(kept[b], want, rtol=1e-5, atol=1e-6)


def test_the_kept_rows_are_rounded_inside_a_call_too():
    """bf16 rows: a call over the whole sequence and two calls that
    split it agree to the bit — where a call ends does not show."""
    spec, p, h, _ = _conv_inputs(6, seed=5)
    rows = jnp.zeros((2, TAPS - 1, D), jnp.bfloat16)
    whole, kept_whole = lfm.conv_mix(spec, p, h, rows, jnp.full((2,), 6))
    a, kept = lfm.conv_mix(spec, p, h[:, :4], rows, jnp.full((2,), 4))
    b, kept = lfm.conv_mix(spec, p, h[:, 4:], kept, jnp.full((2,), 2))
    np.testing.assert_array_equal(jnp.concatenate([a, b], 1), whole)
    np.testing.assert_array_equal(kept, kept_whole)
    assert kept.dtype == jnp.bfloat16


def test_reversed_taps_and_a_missing_gate_are_other_answers():
    spec, p, h, rows = _conv_inputs(5, seed=6)
    n = jnp.full((2,), 5)
    out, _ = lfm.conv_mix(spec, p, h, rows, n)
    flipped, _ = lfm.conv_mix(spec, dict(p, conv_w=p["conv_w"][:, ::-1]), h,
                              rows, n)
    assert np.abs(np.asarray(out - flipped)).max() > 1e-2
    _, g = _conv_by_hand(p, h)
    b = np.split(np.asarray(h, np.float64) @ np.asarray(p["in"]), 3, -1)[0]
    assert np.abs(g - b).max() > 0.1      # g is B * u, not B


# -- attention: the head's norm and the rotation ------------------------------


@pytest.mark.parametrize("norm,gain", [("rmsnorm", "plain"),
                                       ("rmsnorm_unit_offset", "1 + g")])
def test_the_heads_norm_follows_the_specs_norm_kind(norm, gain):
    """`qk_norm` under "rmsnorm" multiplies by the gain w as every norm
    of this family does; under "rmsnorm_unit_offset" by 1 + g, which is
    what Qwen3-Next has."""
    from deepspeed_tpu.models import qwen3_next as qn

    model, params = _model()
    cfg, spec = model.config, model.layer_spec()._replace(norm=norm)
    p = dict(params["blocks"][2]["attn"])
    g = jax.random.normal(jax.random.PRNGKey(2), (DH,)) * 0.5
    p["q_norm"], p["k_norm"] = {"scale": g}, {"scale": g}
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 6, D))
    pos = jnp.arange(6)[None]
    q, k, _, gate = qn.project_gated(cfg, spec, p, h, pos, False,
                                     jnp.float32)
    assert gate is None
    raw = np.asarray(h[0] @ p["q"], np.float64).reshape(6, HEADS, DH)
    normed = raw / np.sqrt((raw ** 2).mean(-1, keepdims=True) + spec.eps)
    by = np.asarray(g, np.float64) + (0.0 if gain == "plain" else 1.0)
    np.testing.assert_allclose(q[0], normed * by, rtol=1e-4, atol=1e-5)


def test_q_and_k_are_normed_then_rotated_over_the_whole_head_by_halves():
    """Pairs i and i + 8 of a head of 16 turn by position x theta^(-i/8);
    the interleaved pairing (2i, 2i + 1) and no norm are other q's."""
    from deepspeed_tpu.models import qwen3_next as qn

    model, params = _model()
    cfg, spec = model.config, model.layer_spec()
    p = params["blocks"][2]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 5, D))
    pos = jnp.arange(5)[None] + 3
    q, k, v, _ = qn.project_gated(cfg, spec, p, h, pos, True, jnp.float32)
    raw = np.asarray(h[0] @ p["k"], np.float64).reshape(5, KV, DH)
    n = raw / np.sqrt((raw ** 2).mean(-1, keepdims=True) + spec.eps)
    ang = (np.arange(5) + 3)[:, None] * 1e4 ** (-np.arange(8) / 8.0)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.concatenate([n[..., :8] * cos - n[..., 8:] * sin,
                           n[..., 8:] * cos + n[..., :8] * sin], -1)
    np.testing.assert_allclose(k[0], want, rtol=1e-4, atol=1e-5)
    for other in (dict(rope_halves=False), dict(qk_norm=False)):
        q2 = qn.project_gated(cfg, spec._replace(**other), p, h, pos, True,
                              jnp.float32)[0]
        assert np.abs(np.asarray(q - q2)).max() > 1e-2


def test_the_heads_norm_removes_the_seeded_scale_of_q_and_k():
    """W_q and W_k are drawn `qk_scale` times as large as the other
    matrices so that the benchmark's check sees a norm left out; with
    the norm the function is the one drawn at scale 1 (but for eps)."""
    tokens = jnp.asarray([_prompt(12, 3)])
    logits = []
    for scale in (1.0, 4.0):
        model = Lfm2Moe(_config(qk_scale=scale))
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        logits.append(np.asarray(model.apply(params, tokens)))
    assert np.abs(logits[0] - logits[1]).max() < 1e-3
    assert Lfm2MoeConfig().qk_scale == 4.0


# -- the experts: bias, epsilon, shares, no shared expert ---------------------


def _moe(model, p, h, **spec_kw):
    from deepspeed_tpu.models.cohere2_moe import expert_ffn

    spec = model.layer_spec()._replace(**spec_kw)
    return np.asarray(expert_ffn(spec, model.config, p, h)[0])


def test_a_routed_layer_is_its_chosen_experts_and_no_shared_one():
    """Against the sum written out expert by expert, the epsilon in the
    quotient; the tree holds no `shared` and none is computed."""
    model, params = _model()
    cfg, p = model.config, params["blocks"][3]["mlp"]
    assert "shared" not in p and "router" not in params["blocks"][1]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(3), (7, D))
    s = np.asarray(jax.nn.sigmoid(h @ p["router"]), np.float64)
    biased = s + np.asarray(p["select_bias"], np.float64)
    hd, want = np.asarray(h, np.float64), np.zeros((7, D))
    silu = lambda a: a / (1 + np.exp(-a))
    for t in range(7):
        top = np.argsort(-biased[t])[:TOPK]
        w = s[t, top] / (s[t, top].sum() + cfg.renorm_eps)
        for e, we in zip(top, w):
            one = {k: np.asarray(a[e], np.float64)
                   for k, a in p["experts"].items()}
            want[t] += we * ((silu(hd[t] @ one["gate"])
                              * (hd[t] @ one["up"])) @ one["down"])
    np.testing.assert_allclose(_moe(model, p, h), want, rtol=2e-4, atol=2e-4)


def test_the_choosing_bias_chooses_and_does_not_weigh():
    model, params = _model()
    p = params["blocks"][2]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(4), (9, D))
    kw = dict(scoring="sigmoid", renormalize=True, renorm_eps=1e-6)
    w0, i0 = dropless.route(h, p["router"], TOPK,
                            select_bias=p["select_bias"], **kw)
    w1, i1 = dropless.route(h, p["router"], TOPK,
                            select_bias=p["select_bias"] + 7.0, **kw)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_allclose(w0, w1, rtol=1e-6)
    forced = jnp.zeros((EXPERTS,)).at[jnp.array([2, 5, 11])].set(10.0)
    w2, i2 = dropless.route(h, p["router"], TOPK, select_bias=forced, **kw)
    assert set(np.asarray(i2).reshape(-1)) == {2, 5, 11}
    s = np.asarray(jax.nn.sigmoid(h @ p["router"]))
    want = np.take_along_axis(s, np.asarray(i2), -1)
    np.testing.assert_allclose(
        w2, want / (want.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # and the seeded bias is large enough to overrule the scores
    _, plain = dropless.route(h, p["router"], TOPK, **kw)
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(i0))).any()


@pytest.mark.parametrize("eps", [0.0, 1e-6, 0.5])
def test_the_epsilon_goes_into_the_sum_that_renormalises(eps):
    """0 is the plain sum every other family has (the same operations
    as before the argument existed); a large one shows."""
    h = jax.random.normal(jax.random.PRNGKey(1), (5, D))
    router = jax.random.normal(jax.random.PRNGKey(2), (D, EXPERTS))
    w, idx = dropless.route(h, router, TOPK, scoring="sigmoid",
                            renormalize=True, renorm_eps=eps)
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(h @ router)),
                           np.asarray(idx), -1)
    np.testing.assert_allclose(w, s / (s.sum(-1, keepdims=True) + eps),
                               rtol=1e-6)
    plain = lambda e: str(jax.make_jaxpr(lambda h: dropless.route(
        h, router, TOPK, scoring="sigmoid", renormalize=True,
        **e))(h))
    assert (plain({}) == plain(dict(renorm_eps=eps))) == (eps == 0.0)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """model-configs section 4: the parts that all eight shares of a
    routed layer give (2 of 16 experts each; no shared expert to count
    once) are the uncut layer."""
    model, params = _model()
    p = params["blocks"][4]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(5), (11, D))
    whole = _moe(model, p, h)
    parts = np.zeros_like(whole)
    for first in range(0, EXPERTS, 2):
        mine = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 2], p["experts"]))
        parts += _moe(model, mine, h, experts_held=2, first_expert=first)
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-4)
    assert np.abs(whole).max() > 0.1


# -- through the programs and the engine --------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("held", [0, 4])
def test_the_engine_matches_the_reference_forward(dtype, held):
    """Seven requests through three slots (slots seated and re-seated
    mid-run, the loop a step ahead), prompts of one, two and three
    chunks of 8 whose lengths are not multiples of it: at every
    generated position the logits the engine drew from are the
    reference's full forward's — with every expert held and with a share
    of four from expert 4 on."""
    from test_evabyte import Probe

    share = dict(experts_held=held, first_expert=held) if held else {}
    model, params = _model(jnp.dtype(dtype), **BF16.get(dtype, {}), **share)
    probe = Probe(model, params, _serve())
    eng = probe.engine
    lengths = [1, 2, 5, 8, 13, 17, 23]
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


def _alone(model, params, prompt, n, **kw):
    return _engine(model, params, **kw).generate([prompt], n)[0]


def test_a_request_does_not_depend_on_its_neighbours():
    model, params = _model()
    prompts = [_prompt(n, 40 + i) for i, n in enumerate((17, 3, 9, 12))]
    together = _engine(model, params).generate(prompts, 8)
    for prompt, out in zip(prompts, together):
        assert _alone(model, params, prompt, 8) == out


def test_a_slot_that_is_not_running_gets_its_rows_back_unchanged():
    """One request through an engine of three slots whose kept rows
    were filled with noise: its prefill chunks and every decode step
    hand the other two slots' rows back to the bit, and its own start
    from zeros and move."""
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    for i in CONV_LAYERS:
        eng.kv.caches[i] = tuple(
            jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
            for a in eng.kv.caches[i])
    before = {i: np.asarray(eng.kv.caches[i][0]) for i in CONV_LAYERS}
    req = eng.submit(_prompt(11), 5)
    eng.step()
    slot = req.slot
    eng.run()
    assert req.state == "finished" and slot is not None
    others = [s for s in range(3) if s != slot]
    for i in CONV_LAYERS:
        rows = np.asarray(eng.kv.caches[i][0])
        np.testing.assert_array_equal(rows[others], before[i][others])
        assert np.abs(rows[slot] - before[i][slot]).max() > 0
    lg = np.asarray(model.apply(params, jnp.asarray([req.prompt + req.out])))
    assert req.out == lg[0, 10:-1].argmax(-1).tolist()


def test_a_seated_slot_starts_from_zeros(monkeypatch):
    """One slot, four requests one after another, each ending on an
    `eos_token` the loop finds a step late — the retired slot is stepped
    once more — and the next request is seated in it at once: each
    answer is the one the request gets in a fresh engine.  Without the
    zeroing at seating it is not."""
    model, params = _model()
    prompts = [_prompt(n, 20 + i) for i, n in enumerate((9, 2, 13, 5))]
    full = [_alone(model, params, p, 6, max_batch=1) for p in prompts]
    eos = [out[2] for out in full]
    want = [out[:out.index(e) + 1] for out, e in zip(full, eos)]

    def serve_all():
        eng = _engine(model, params, max_batch=1)
        before = COUNTERS.snapshot()
        reqs = [eng.submit(p, 6, eos_token=e) for p, e in zip(prompts, eos)]
        eng.run()
        return [r.out for r in reqs], COUNTERS.delta_since(before)

    outs, d = serve_all()
    assert outs == want
    assert d["serve.conv.state_resets"]["calls"] == 4
    monkeypatch.setattr(PagedKVCache, "reset_state", lambda self, slot: None)
    assert serve_all()[0] != want


def test_two_rows_a_slot_in_a_conv_layer_and_rows_where_it_attends():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    kv = eng.kv
    width = serving_layers.pool_rows(
        jnp.zeros((1, KV, DH)), kv.caches[2][0].shape[1]).shape[1]
    for i, entry in enumerate(kv.caches):
        if i in CONV_LAYERS:
            assert [a.shape for a in entry] == [(3, TAPS - 1, D)]
            assert entry[0].dtype == kv.caches[2][0].dtype
        else:
            assert [a.shape for a in entry] == [(64 * BS, width)] * 2
    assert kv.state_nbytes() == 3 * len(CONV_LAYERS) * (TAPS - 1) * D * 4
    rows = 2 * len(ROW_LAYERS) * 64 * BS * width * 4
    assert kv.nbytes() - kv.state_nbytes() == rows


def test_counters_of_the_convolutions_and_the_experts():
    """`serve.conv.*`, name for name with `serve.ssm.*`: every slot's
    rows read and written a step (no kernel walks the live ones),
    `serve.moe.*` over the six routed layers and `serve.attn.*` over the
    two that attend."""
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    assert eng._routed_layers == len(TYPES) - DENSE
    assert STATE_MIXERS[eng.plan.groups[0].kind].counters == "serve.conv"
    assert eng._counted["conv"].walks == ((False, None),)  # no slot's is dead
    before = COUNTERS.snapshot()
    eng.generate([_prompt(9, 1), _prompt(5, 2)], 6)
    d = COUNTERS.delta_since(before)
    steps = d["serve.decode_steps"]["calls"]
    assert d["serve.conv.state_bytes"]["calls"] == steps
    assert d["serve.conv.state_bytes"]["bytes"] == \
        steps * 2 * eng.kv.state_nbytes()
    assert d["serve.conv.slots_live"]["bytes"] == \
        len(CONV_LAYERS) * d["serve.decode_steps"]["bytes"]
    assert d["serve.conv.state_resets"]["calls"] == 2
    assert not any(k.startswith(("serve.ssm.", "serve.gdn.")) for k in d)
    touched = d["serve.moe.experts_touched"]
    assert touched["calls"] == steps * (len(TYPES) - DENSE)
    assert d["serve.attn.rows_read"]["bytes"] > 0


def test_engine_refuses_by_name():
    from deepspeed_tpu.comm import make_mesh

    model, params = _model()
    with pytest.raises(NotImplementedError, match="prefix_cache=True over "
                       "layers with a state"):
        ServeEngine(model, params, _serve(prefix_cache=True))
    with pytest.raises(NotImplementedError, match="draft_len > 0 over "
                       "layers with a state"):
        ServeEngine(model, params, _serve(draft_len=2))
    with pytest.raises(NotImplementedError, match="kv_dtype 'int8' over "
                       "layers with a state"):
        ServeEngine(model, params, _serve(kv_dtype="int8"))
    with pytest.raises(NotImplementedError,
                       match="a mesh of 2 devices over layers with a state"):
        ServeEngine(model, params, _serve(),
                    mesh_info=make_mesh(model=2, data=1, set_current=False,
                                        devices=jax.devices()[:2]))
    eng = ServeEngine(model, params, _serve())
    with pytest.raises(NotImplementedError,
                       match="sessions over layers with a state"):
        eng.submit(_prompt(5), 4, session_id="s")


# -- the spec: the one table, and its misuse ----------------------------------


def test_one_table_of_state_mixers_that_three_kinds_read():
    """Every kind of mixer that keeps arrays by slot is one entry: what
    a slot keeps, the mix function (loaded when asked for), the
    counters' family and the step kernel, or none."""
    assert tuple(STATE_MIXERS) == ("ssm", "gdn", "conv")
    assert [k.counters for k in STATE_MIXERS.values()] == [
        "serve.ssm", "serve.gdn", "serve.conv"]
    conv = STATE_MIXERS["conv"]
    assert conv.step_kernel is None and conv.mix_fn() is lfm.conv_mix
    from deepspeed_tpu.models import granite_hybrid as gh

    assert STATE_MIXERS["ssm"].mix_fn() is gh.ssm_mix
    state = jax.ShapeDtypeStruct((4, 8, 4, 8), jnp.float32)
    spec = LayerSpec(norm="rmsnorm", positions="none", attention="grouped",
                     ffn="silu_gated", head="tied", eps=1e-5, kv_heads=2,
                     layer_mixers=("ssm", "attention"), ssm_heads=8,
                     ssm_head_dim=4, ssm_state=8, ssm_conv=4, ssm_chunk=4,
                     ssm_groups=2).validate()
    op, info = STATE_MIXERS["ssm"].step_kernel(spec, (state,))
    assert op == "ssm_step" and info["groups"] == 2
    assert spec.state_mixer == "ssm" and spec.state_chunk == 4
    assert spec.state_shapes == (((8, 4, 8), "float32"), ((3, 64), None))


def _spec(**kw):
    return _model()[0].layer_spec()._replace(**kw)


@pytest.mark.parametrize("change,match", [
    (dict(conv_taps=0), "conv_taps >= 2"),
    (dict(conv_taps=1), "conv_taps >= 2"),
    (dict(conv_channels=0), "conv_channels"),
    (dict(layer_mixers=("attention",) * 8,
          layer_positions=("rope",) * 8), "a pattern with conv layers"),
    (dict(layer_mixers=("conv", "ssm", "attention", "conv") * 2,
          ssm_heads=2, ssm_head_dim=4, ssm_state=4, ssm_conv=2, ssm_chunk=4),
     "of one kind"),
    (dict(layer_mixers=("conv", "gdn", "attention", "conv") * 2),
     "of one kind"),
    (dict(renorm_eps=-1.0), "renorm_eps"),
    (dict(renormalize=False), "renorm_eps"),
    (dict(ffn="silu_gated", top_k=0, dense_layers=0, scoring="softmax",
          renormalize=False, select_bias=False), "renorm_eps"),
])
def test_layer_spec_validate_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        _spec(**change).validate()


@pytest.mark.parametrize("change,match", [
    (dict(layer_positions=("rope",) * 8),
     "a layer that keeps a state has no positions"),
    (dict(norm="layernorm"), "a hybrid of state layers"),
    (dict(layer_windows=(0, 0, 8, 0)), "a hybrid of state layers"),
    (dict(residual="parallel"), "layers with a state"),
])
def test_serving_refuses_the_neighbours_it_has_not_built(change, match):
    with pytest.raises(NotImplementedError, match=match):
        serving_layers.check_spec(_spec(**change))


def test_other_families_take_none_of_the_new_branches():
    spec = LayerSpec(norm="layernorm", positions="learned", attention="paged",
                     ffn="gelu_mlp", head="tied", eps=1e-5).validate()
    assert (spec.conv_taps, spec.conv_channels, spec.renorm_eps) == (0, 0, 0)
    assert spec.state_mixer is None and spec.state_shapes == ()
    assert spec.state_chunk == 0 and not spec.has_state


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
            "bad = [m for m in sys.modules if 'lfm2' in m]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})


@pytest.mark.parametrize("family", ["granite_hybrid", "qwen3_next",
                                    "nemotron_h"])
def test_no_other_familys_path_imports_this_one(family):
    """Building another state family's engine and its programs loads
    nothing of this family: the table names the mix function and loads
    it when a block of the kind is built."""
    import subprocess
    import sys

    code = ("import sys, jax; sys.path.insert(0, 'tests'); "
            "from test_program_scopes import FAMILIES; "
            "from deepspeed_tpu.serving import ServeConfig, ServeEngine; "
            f"model, serve = FAMILIES['{family}'](); "
            "params = jax.jit(model.init)(jax.random.PRNGKey(0)); "
            "eng = ServeEngine(model, params, ServeConfig(**serve)); "
            "eng.generate([[1, 2, 3]], 2); "
            "bad = [m for m in sys.modules if 'lfm2' in m]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})
