"""Nemotron-H through the serving engine: layers that are each ONE part
— a Mamba-2 mixer of several groups, grouped attention without
positions, or sigmoid-routed two-matrix relu2 experts beside a shared
one — against the plain reference (`benchmarks/reference/nemotron_h.py`,
the recurrence only) on seeded weights at toy widths: 10 layers
"MEM*EMEM*E", hidden 32, 4 query heads on 2 K/V heads of 16, 8
state-space heads of 4 in 4 groups over a state of 8, convolution 4,
scan chunk 4, 16 experts top 3 of width 16, a shared one of 24,
vocabulary 97.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import nemotron_h as ref
from deepspeed_tpu.models import LayerSpec
from deepspeed_tpu.models import granite_hybrid as gh
from deepspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (PagedKVCache, ServeConfig, ServeEngine,
                                   ServeProgramBuilder, ServeSchedule)
from deepspeed_tpu.serving import layers as serving_layers
from toy_plans import toy_plan

VOCAB, PATTERN = 97, "MEM*EMEM*E"
HEADS, KV, DH = 4, 2, 16
SH, SP, SN, SG, TAPS, SCAN = 8, 4, 8, 4, 4, 4
EXPERTS, TOPK, FF, FS = 16, 3, 16, 24
BS, CHUNK, SEQ = 4, 8, 64
STATE_LAYERS, ROW_LAYERS, FFN_LAYERS = (0, 2, 5, 7), (3, 8), (1, 4, 6, 9)
CONV = SH * SP + 2 * SG * SN


def _config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=SEQ, pattern=PATTERN,
                d_model=32, num_heads=HEADS, kv_heads=KV, head_dim=DH,
                ssm_heads=SH, ssm_head_dim=SP, ssm_state=SN, ssm_groups=SG,
                ssm_conv=TAPS, ssm_chunk=SCAN, d_expert=FF, d_shared=FS,
                num_experts=EXPERTS, top_k=TOPK, init_std=0.2,
                bias_std=0.05)
    base.update(kw)
    return NemotronHConfig(**base)


def _kw(cfg):
    return dict(pattern=cfg.pattern, heads=cfg.num_heads,
                kv_heads=cfg.kv_heads, ssm_heads=cfg.ssm_heads,
                state=cfg.ssm_state, groups=cfg.ssm_groups, top_k=cfg.top_k,
                first_expert=cfg.first_expert, route_scale=cfg.route_scale,
                eps=cfg.norm_eps)


def _serve(**kw):
    base = dict(block_size=BS, num_blocks=64, max_batch=3,
                prefill_chunk=CHUNK, max_seq_len=SEQ, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


_MODELS = {}


def _model(dtype=jnp.float32, **kw):
    key = (jnp.dtype(dtype).name, repr(sorted(kw.items())))
    if key not in _MODELS:
        model = NemotronH(_config(param_dtype=dtype, **kw))
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


def _forced(*ops):
    """The registry's own override: the named kernels (all three this
    family reaches, by default) under the Pallas interpreter."""
    from deepspeed_tpu.kernels import kernel_config

    ops = ops or ("ssm_step", "touched_experts", "grouped_experts")
    return kernel_config(ops={op: "pallas" for op in ops}, interpret=True)


# the logits have a standard deviation of ~1.2.  float32: the largest
# difference, under a ten-thousandth of that (the chunked scan sums a
# chunk in another order than the recurrence, a routing weight is a
# quotient of sigmoid scores, ten layers).  bf16: the mean difference, on
# weights drawn at a quarter of the float32 tests' scale — the inputs of
# every product rounded to 8 bits of mantissa, and a squared ReLU doubles
# a relative error
TOL = {"float32": 1e-4, "bfloat16": 1e-2}
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
    assert want.std() > (0.5 if dtype == "float32" else 0.1)
    assert _differ(got, want, dtype) < TOL[dtype]


def test_reference_is_independent_of_the_model_under_test():
    import inspect

    src = inspect.getsource(ref)
    assert "deepspeed_tpu" not in src.replace(
        "`deepspeed_tpu.models.nemotron_h\n.NemotronH.init`", "")
    assert "import deepspeed_tpu" not in src and "from deepspeed_tpu" \
        not in src


def test_the_published_pattern_is_one_part_a_layer():
    """52 layers: 23 mixers, 23 expert layers, 6 attention layers at 5,
    12, 19, 26, 33 and 42, a mixer straight before each; the spec says
    of each layer which single part it is and who owns what."""
    cfg = NemotronHConfig()
    spec = NemotronH(cfg).layer_spec()
    n = cfg.num_layers
    assert n == 52 and spec.residual == "single"
    assert spec.row_layers(n) == (5, 12, 19, 26, 33, 42)
    assert len(spec.state_layers(n)) == 23
    assert len(spec.routed_layers(n)) == 23
    assert all(spec.mixer_of(i - 1) == "ssm" for i in spec.row_layers(n))
    assert sorted(spec.row_layers(n) + spec.state_layers(n)
                  + spec.routed_layers(n)) == list(range(n))
    assert all(spec.has_ffn(i) == (cfg.pattern[i] == "E") for i in range(n))
    assert spec.ssm_conv_width == 4096 + 2 * 8 * 128 == cfg.conv_width
    assert spec.state_shapes == (((64, 64, 128), "float32"),
                                 ((3, 6144), None))
    assert spec.ssm_groups == 8


# -- the mixer: groups --------------------------------------------------------


def _mixer_inputs(T, groups, seed=0, B=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (B, T, groups, SN) if groups > 1 else (B, T, SN)
    x = jax.random.normal(k[0], (B, T, SH, SP))
    Bm = jax.random.normal(k[1], shape)
    Cm = jax.random.normal(k[2], shape)
    dt = jax.random.uniform(k[3], (B, T, SH), minval=0.01, maxval=0.5)
    A = -jax.random.uniform(k[4], (SH,), minval=0.5, maxval=4.0)
    state = jax.random.normal(k[5], (B, SH, SP, SN))
    return x, Bm, Cm, dt, A, state


def _by_hand(x, Bm, Cm, dt, A, state, groups):
    """The recurrence written out head by head: head h reads the B and C
    of group h // (heads / groups)."""
    x, Bm, Cm, dt, A = (np.asarray(a, np.float64)
                        for a in (x, Bm, Cm, dt, A))
    S = np.asarray(state, np.float64).copy()
    if groups == 1:
        Bm, Cm = Bm[:, :, None], Cm[:, :, None]
    ys = np.zeros(x.shape)
    for t in range(x.shape[1]):
        for h in range(SH):
            g = h // (SH // groups)
            a = np.exp(dt[:, t, h] * A[h])[:, None, None]
            S[:, h] = a * S[:, h] + (dt[:, t, h, None] * x[:, t, h])[
                :, :, None] * Bm[:, t, g][:, None, :]
            ys[:, t, h] = np.einsum("bpn,bn->bp", S[:, h], Cm[:, t, g])
    return ys, S


@pytest.mark.parametrize("groups", [1, 2, 4, 8])
@pytest.mark.parametrize("T,chunk", [(4, 4), (8, 4), (12, 2), (6, 6)])
def test_the_chunked_scan_is_the_recurrence_at_any_groups(T, chunk, groups):
    """`ssm_scan` (through `by_group` past one group) and the step, both
    against the recurrence head by head in float64."""
    x, Bm, Cm, dt, A, state = _mixer_inputs(T, groups, seed=T + groups)
    scan = gh.ssm_scan if groups == 1 else gh.by_group(gh.ssm_scan, groups)
    step = gh.ssm_step if groups == 1 else gh.by_group(gh.ssm_step, groups)
    want_y, want_s = _by_hand(x, Bm, Cm, dt, A, state, groups)
    y, s = scan(x, Bm, Cm, dt, A, state, chunk)
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=2e-4)
    ys, s = [], state
    for t in range(T):
        y_t, s = step(x[:, t], Bm[:, t], Cm[:, t], dt[:, t], A, s)
        ys.append(y_t)
    np.testing.assert_allclose(jnp.stack(ys, 1), want_y, atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=2e-4)


def test_group_zero_for_every_head_is_another_answer():
    x, Bm, Cm, dt, A, state = _mixer_inputs(4, 4)
    y, _ = gh.by_group(gh.ssm_scan, 4)(x, Bm, Cm, dt, A, state, 4)
    y0, _ = gh.ssm_scan(x, Bm[:, :, 0], Cm[:, :, 0], dt, A, state, 4)
    np.testing.assert_allclose(y[:, :, :2], y0[:, :, :2], atol=1e-5)
    assert np.abs(np.asarray(y - y0)[:, :, 2:]).max() > 0.1


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_gated_norm_is_taken_group_by_group(groups):
    spec = _model()[0].layer_spec()._replace(ssm_groups=groups)
    g = jax.random.normal(jax.random.PRNGKey(groups), (2, 3, SH * SP)) * 3
    gain = jax.random.normal(jax.random.PRNGKey(9), (SH * SP,))
    got = gh.gated_norm(spec, g, {"scale": gain})
    parts = np.asarray(g, np.float64).reshape(2, 3, groups, -1)
    want = parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + spec.eps)
    np.testing.assert_allclose(got, want.reshape(2, 3, -1) * np.asarray(gain),
                               rtol=1e-5, atol=1e-6)
    if groups > 1:      # and it is not the norm over all of them
        whole = gh.gated_norm(spec._replace(ssm_groups=1), g,
                              {"scale": gain})
        assert np.abs(np.asarray(got - whole)).max() > 0.05


@pytest.mark.parametrize("live", [
    (0, 0, 0, 0), (1, 1, 1, 1), (1, 0, 1, 0), (0, 0, 0, 1)],
    ids=["none", "all", "scattered", "last"])
@pytest.mark.parametrize("tiles,groups", [(1, 8), (1, 2), (2, 8), (4, 2),
                                          (1, 1)])
def test_the_kernel_steps_each_head_with_its_groups_b_and_c(live, tiles,
                                                            groups,
                                                            monkeypatch):
    """The `ssm_step` kernel at 16 heads of 64 over a state of 128 (a
    group's rows are whole blocks of 128) against `by_group(ssm_step)`:
    a tile of whole groups, a group of whole tiles, one group."""
    from deepspeed_tpu.kernels import registry, ssm

    H, P, N, B = 16, 64, 128, 4
    if tiles > 1:
        monkeypatch.setattr(ssm, "_STATE_BLOCK_BYTES",
                            4 * (H // tiles) * P * N * 4)
    th = ssm.head_tile(H, P, N)
    assert th == H // tiles and ssm.groups_fit(H, P, groups, th)
    k = jax.random.split(jax.random.PRNGKey(groups + tiles), 6)
    shape = (B, groups, N) if groups > 1 else (B, N)
    x = jax.random.normal(k[0], (B, H, P))
    Bm, Cm = jax.random.normal(k[1], shape), jax.random.normal(k[2], shape)
    on = np.asarray(live, bool)
    dt = jax.random.uniform(k[3], (B, H), minval=0.01, maxval=0.5) * \
        jnp.asarray(on, jnp.float32)[:, None]
    A = -jax.random.uniform(k[4], (H,), minval=0.5, maxval=4.0)
    state = jax.random.normal(k[5], (B, H, P, N))
    ids, n = ssm.live_slots(jnp.asarray(live))
    step = gh.ssm_step if groups == 1 else gh.by_group(gh.ssm_step, groups)
    want_y, want_state = step(x, Bm, Cm, dt, A, state)
    info = ssm.ssm_step_info(state, groups)
    assert registry.get_kernel("ssm_step").auto_supports(
        "default", info) == (True, "")
    with _forced("ssm_step"):
        y, got = jax.jit(lambda *a: registry.dispatch(
            "ssm_step", *a, info=info))(x, Bm, Cm, dt, A, state, ids, n)
    y, got = np.asarray(y), np.asarray(got)
    np.testing.assert_array_equal(got[~on], np.asarray(state)[~on])
    np.testing.assert_array_equal(y[~on], 0.0)
    np.testing.assert_allclose(got[on], np.asarray(want_state)[on],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y[on], np.asarray(want_y)[on], atol=1e-4,
                               rtol=1e-4)


def test_the_kernel_refuses_groups_that_split_a_block_of_rows():
    from deepspeed_tpu.kernels import registry
    from deepspeed_tpu.kernels.ssm import ssm_step_info

    state = jax.ShapeDtypeStruct((40, 64, 64, 128), jnp.float32)
    op = registry.get_kernel("ssm_step")
    assert op.auto_supports("default", ssm_step_info(state, 8)) == (True, "")
    assert op.auto_supports("default", ssm_step_info(state)) == (True, "")
    ok, why = op.auto_supports("default", ssm_step_info(state, 64))
    assert not ok and "do not fall on blocks of 128 rows" in why


# -- the experts: form, bias, shares ------------------------------------------


def _expert_layer(cfg, params, layer=1):
    return params["blocks"][layer]["mlp"]


def _moe(model, p, h, **spec_kw):
    from deepspeed_tpu.models.cohere2_moe import expert_ffn

    spec = model.layer_spec()._replace(**spec_kw)
    return np.asarray(expert_ffn(spec, model.config, p, h)[0])


def test_an_expert_is_two_matrices_and_a_squared_relu():
    """Against the sum written out expert by expert; and a SiLU-gated
    expert over the same `up` and `down` is another function."""
    model, params = _model()
    cfg, p = model.config, _expert_layer(model.config, params)
    h = jax.random.normal(jax.random.PRNGKey(3), (7, 32))
    s = np.asarray(jax.nn.sigmoid(h @ p["router"]), np.float64)
    biased = s + np.asarray(p["select_bias"], np.float64)
    want = np.zeros((7, 32))
    hd = np.asarray(h, np.float64)
    relu2 = lambda e: np.maximum(hd @ np.asarray(e["up"], np.float64),
                                 0) ** 2 @ np.asarray(e["down"], np.float64)
    for t in range(7):
        top = np.argsort(-biased[t])[:TOPK]
        w = s[t, top] / s[t, top].sum() * cfg.route_scale
        for e, we in zip(top, w):
            one = jax.tree_util.tree_map(lambda a: a[e], p["experts"])
            want[t] += we * relu2(one)[t]
    want += relu2(p["shared"])
    got = _moe(model, p, h)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    gated = dict(p, experts=dict(p["experts"], gate=p["experts"]["up"]),
                 shared=dict(p["shared"], gate=p["shared"]["up"]))
    assert dropless.expert_matrices(p["experts"]) == 2
    assert dropless.expert_matrices(gated["experts"]) == 3
    assert np.abs(_moe(model, gated, h) - got).max() > 0.1


def test_the_choosing_bias_chooses_and_does_not_weigh():
    """A bias large enough to force the choice moves WHICH experts a
    token gets; the weights stay the unbiased scores over their sum, so
    a bias added to every expert alike changes nothing."""
    model, params = _model()
    p = _expert_layer(model.config, params)
    h = jax.random.normal(jax.random.PRNGKey(4), (9, 32))
    kw = dict(scoring="sigmoid", renormalize=True, scale=2.5)
    w0, i0 = dropless.route(h, p["router"], TOPK,
                            select_bias=p["select_bias"], **kw)
    w1, i1 = dropless.route(h, p["router"], TOPK,
                            select_bias=p["select_bias"] + 7.0, **kw)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_allclose(w0, w1, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 2.5, rtol=1e-5)
    forced = jnp.zeros((EXPERTS,)).at[jnp.array([2, 5, 11])].set(10.0)
    w2, i2 = dropless.route(h, p["router"], TOPK, select_bias=forced, **kw)
    assert set(np.asarray(i2).reshape(-1)) == {2, 5, 11}
    s = np.asarray(jax.nn.sigmoid(h @ p["router"]))
    want = np.take_along_axis(s, np.asarray(i2), -1)
    np.testing.assert_allclose(w2, want / want.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-5)
    # and the seeded bias is large enough to overrule the scores
    _, plain = dropless.route(h, p["router"], TOPK, **kw)
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(i0))).any()


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """model-configs section 4: the parts that all eight shares of an
    expert layer give (2 of 16 experts each), the shared expert counted
    once, are the uncut layer."""
    model, params = _model()
    p = _expert_layer(model.config, params)
    h = jax.random.normal(jax.random.PRNGKey(5), (11, 32))
    whole = _moe(model, p, h)
    shared = np.asarray(dropless.dense_expert(p["shared"], h))
    parts = np.zeros_like(whole)
    for first in range(0, EXPERTS, 2):
        mine = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 2], p["experts"]))
        parts += _moe(model, mine, h, experts_held=2,
                      first_expert=first) - shared
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-4, atol=1e-4)
    assert np.abs(whole - shared).max() > 0.1


@pytest.mark.parametrize("module", ["kernels/moe_kernels.py",
                                    "kernels/expert_form.py"])
def test_the_kernels_import_nothing_above_them(module):
    """An expert's form lies under both of its callers
    (kernels/expert_form.py): the kernels' bodies and the module that
    chooses which kernel to call (moe/dropless.py) read it, and no
    kernel module imports the layer above it, at the top or in a
    function."""
    import ast
    import os

    import deepspeed_tpu

    path = os.path.join(os.path.dirname(deepspeed_tpu.__file__), module)
    froms = [n for n in ast.walk(ast.parse(open(path).read()))
             if isinstance(n, ast.ImportFrom)]
    assert froms
    for n in froms:
        above = n.level == 2 and (n.module or "").split(".")[0] in (
            "moe", "models", "serving")
        assert not above, (module, n.module)


def test_the_engine_reads_an_experts_form_off_the_tree():
    """No field of the spec says what an expert is: one spec serves a
    tree of two matrices an expert and the same tree with a `gate`
    (three), each as its own form."""
    model, params = _model()
    spec = model.layer_spec()
    assert not hasattr(spec, "expert_form")
    blocks = list(params["blocks"])
    for i in spec.routed_layers(model.config.num_layers):
        mlp = blocks[i]["mlp"]
        blocks[i] = dict(blocks[i], mlp=dict(
            mlp, experts=dict(mlp["experts"], gate=mlp["experts"]["up"]),
            shared=dict(mlp["shared"], gate=mlp["shared"]["up"])))
    gated = dict(params, blocks=type(params["blocks"])(blocks))
    prompt, outs = _prompt(9), []
    for tree in (params, gated):
        out = ServeEngine(model, tree, _serve()).generate([prompt], 4)[0]
        lg = np.asarray(model.apply(tree, jnp.asarray([prompt + out])))[0]
        assert out == lg[len(prompt) - 1:-1].argmax(-1).tolist()
        outs.append(lg[len(prompt) - 1])
    assert np.abs(outs[0] - outs[1]).max() > 1e-3


@pytest.mark.parametrize("F", [256, 192])
@pytest.mark.parametrize("matrices", [2, 3])
@pytest.mark.parametrize("way", ["touched", "slabs"])
def test_both_kernels_take_either_form(way, matrices, F):
    """The touched-experts walk and the slab walk under the interpreter
    against their oracles, with and without a `gate`; at 192 columns,
    not whole 128-lane tiles, the front matrices go to the kernel turned
    (`moe_kernels._turned`: the chip holds such an array with D on the
    lanes, and the kernel takes it as it lies)."""
    from deepspeed_tpu.kernels import moe_kernels

    assert moe_kernels._turned(F) == (F == 192)
    assert moe_kernels._turned(1856) and not moe_kernels._turned(1408)
    E, D, T, k = 8, 128, 24, 2
    keys = jax.random.split(jax.random.PRNGKey(matrices), 5)
    experts = {"up": jax.random.normal(keys[0], (E, D, F)) * 0.1,
               "down": jax.random.normal(keys[1], (E, F, D)) * 0.1}
    if matrices == 3:
        experts["gate"] = jax.random.normal(keys[2], (E, D, F)) * 0.1
    x = jax.random.normal(keys[3], (T, D))
    idx = jax.random.randint(keys[4], (T, k), 0, E)
    weights = jnp.full((T, k), 0.5)
    assert dropless.touched_info(T, experts)["matrices"] == matrices
    if way == "touched":
        want = dropless.experts_masked(x, experts, weights, idx)
        with _forced("touched_experts"):
            got = dropless.experts_touched_only(x, experts, weights, idx)
    else:
        want = dropless.experts_grouped(x, experts, weights, idx)
        with _forced("grouped_experts"):
            got = dropless.experts_slabs(x, experts, weights, idx)
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_a_tile_counts_the_operands_an_expert_has():
    """At the published width, 1,856 = 14.5 x 128, no whole-tile share
    divides an expert's columns: three operands of the whole width do
    not fit the kernel's VMEM, two do."""
    from deepspeed_tpu.kernels import registry
    from deepspeed_tpu.kernels.moe_kernels import grouped_tile, touched_tile

    assert touched_tile(2688, 1856, 2, 3) == 0
    assert touched_tile(2688, 1856, 2, 2) == 1856
    assert touched_tile(2688, 1856, 2) == 0         # three, as it was
    assert grouped_tile(1024, 2688, 1856, 2, 2) == 1856
    shapes = {"up": jax.ShapeDtypeStruct((16, 2688, 1856), jnp.bfloat16),
              "down": jax.ShapeDtypeStruct((16, 1856, 2688), jnp.bfloat16)}
    op = registry.get_kernel("touched_experts")
    assert op.auto_supports("default",
                            dropless.touched_info(40, shapes)) == (True, "")
    gated = dict(shapes, gate=shapes["up"])
    ok, why = op.auto_supports("default", dropless.touched_info(40, gated))
    assert not ok and "3 matrices an expert" in why
    op = registry.get_kernel("grouped_experts")
    rows = dropless.slab_rows(512, 6, 16, 128)
    assert op.auto_supports("default", dropless.grouped_info(
        512, rows, shapes)) == (True, "")


# -- through the programs and the engine --------------------------------------


@pytest.mark.parametrize("way", ["oracle", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_engine_matches_the_reference_forward(dtype, way):
    """Seven requests through three slots (slots seated and re-seated
    mid-run, the loop a step ahead), prompts from 1 token to three
    chunks: at every generated position the logits the engine drew from
    are the reference's full forward's."""
    from test_evabyte import Probe

    model, params = _model(jnp.dtype(dtype), **BF16.get(dtype, {}))
    with _forced() if way == "kernel" else contextlib.nullcontext():
        probe = Probe(model, params, _serve())
        eng = probe.engine
        lengths = [1, 2, 5, 8, 13, 17, 24]
        reqs = [eng.submit(_prompt(n, i), 4 + i)
                for i, n in enumerate(lengths)]
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


@pytest.mark.parametrize("way", ["oracle", "kernel"])
def test_a_seated_slot_starts_from_zeros(way, monkeypatch):
    """One slot, four requests one after another, each ending on an
    `eos_token` the loop finds a step late — the retired slot is stepped
    once more — and the next request is seated in it at once: each
    answer is the one the request gets in a fresh engine.  Without the
    zeroing at seating it is not."""
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
        assert d["serve.ssm.state_resets"]["calls"] == 4
        monkeypatch.setattr(PagedKVCache, "reset_state",
                            lambda self, slot: None)
        assert serve_all()[0] != want


def test_rows_for_the_attention_layers_and_nothing_for_an_expert_layer():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    kv = eng.kv
    width = serving_layers.pool_rows(
        jnp.zeros((1, KV, DH)), kv.caches[3][0].shape[1]).shape[1]
    for i, entry in enumerate(kv.caches):
        if i in STATE_LAYERS:
            assert [a.shape for a in entry] == [(3, SH, SP, SN),
                                                (3, TAPS - 1, CONV)]
            assert entry[0].dtype == jnp.float32
        elif i in ROW_LAYERS:
            assert [a.shape for a in entry] == [(64 * BS, width)] * 2
        else:
            assert entry == ()
    rows = 2 * len(ROW_LAYERS) * 64 * BS * width * 4
    assert kv.nbytes() - kv.state_nbytes() == rows
    assert kv.bytes_per_block() == rows // 64
    assert "4 layer(s) with neither" in kv.describe()
    with pytest.raises(ValueError, match="layers that own nothing"):
        toy_plan(3, KV, DH, BS, 16, attention="grouped", kv_heads=KV,
                 layer_mixers=("attention", "none", "attention"))


@pytest.mark.parametrize("way", ["oracle", "kernel"])
def test_counters_of_the_mixers_and_the_experts(way):
    """`serve.ssm.*` counts the four mixers' state (8 heads of 4 x 8
    float32 and 3 kept inputs of 96, a slot), `serve.moe.*` the four
    expert layers — not `num_layers - dense_layers` of them."""
    model, params = _model()
    with _forced() if way == "kernel" else contextlib.nullcontext():
        eng = ServeEngine(model, params, _serve())
        assert eng._routed_layers == len(FFN_LAYERS)
        before = COUNTERS.snapshot()
        eng.generate([_prompt(9, 1), _prompt(5, 2)], 6)
    d = COUNTERS.delta_since(before)
    steps = d["serve.decode_steps"]["calls"]
    state = 3 * len(STATE_LAYERS) * (SH * SP * SN * 4 + (TAPS - 1) * CONV * 4)
    assert eng.kv.state_nbytes() == state
    assert d["serve.ssm.state_bytes"]["calls"] == steps
    assert d["serve.ssm.slots_live"]["bytes"] == \
        len(STATE_LAYERS) * d["serve.decode_steps"]["bytes"]
    touched = d["serve.moe.experts_touched"]
    assert touched["calls"] == steps * len(FFN_LAYERS)
    assert 0 < touched["bytes"] <= touched["calls"] * EXPERTS
    assert d["serve.moe.prefill_rows_multiplied"]["calls"] == \
        d["serve.prefill_chunks"]["calls"] * len(FFN_LAYERS)
    assert d["serve.attn.rows_read"]["bytes"] > 0


def test_engine_refuses_by_name():
    model, params = _model()
    with pytest.raises(NotImplementedError, match="prefix_cache=True over "
                       "layers with a state"):
        ServeEngine(model, params, _serve(prefix_cache=True))
    with pytest.raises(NotImplementedError, match="draft_len > 0 over "
                       "layers with a state"):
        ServeEngine(model, params, _serve(draft_len=2))


def _spec(**kw):
    return _model()[0].layer_spec()._replace(**kw)


@pytest.mark.parametrize("change,match", [
    (dict(residual="sequential"), "layers that are their FFN alone"),
    (dict(layer_mixers=("ssm", "attention")), "layers that are their FFN"),
    (dict(layer_mixers=("none", "none")), "a pattern with ssm layers"),
    (dict(ssm_groups=3), "ssm_groups that divide the heads"),
    (dict(ssm_groups=0), "ssm_groups"),
])
def test_layer_spec_validate_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        _spec(**change).validate()


@pytest.mark.parametrize("change,match", [
    (dict(positions="per_layer",
          layer_positions=("none",) * len(PATTERN)), "one part a layer"),
    (dict(layer_windows=(0, 4)), "no window"),
    (dict(norm="layernorm"), "RMSNorm"),
    (dict(residual="parallel",
          layer_mixers=("ssm", "attention")), "hybrid of state layers"),
])
def test_serving_refuses_the_neighbours_it_has_not_built(change, match):
    with pytest.raises(NotImplementedError, match=match):
        serving_layers.check_spec(_spec(**change))


def test_other_families_take_none_of_the_new_branches():
    """A spec that names none of the new fields has one group, an FFN in
    every layer and rows in every layer that attends."""
    spec = LayerSpec(norm="rmsnorm", positions="none", attention="grouped",
                     ffn="silu_gated", head="tied", eps=1e-5, kv_heads=2,
                     layer_mixers=("ssm", "attention"), ssm_heads=4,
                     ssm_head_dim=8, ssm_state=16, ssm_conv=4,
                     ssm_chunk=4).validate()
    assert spec.ssm_groups == 1
    assert spec.ssm_conv_width == 4 * 8 + 2 * 16
    assert all(spec.has_ffn(i) for i in range(4))
    assert spec.row_layers(4) == (1, 3) and spec.state_layers(4) == (0, 2)
    assert spec.routed_layers(4) == ()
    routed = LayerSpec(norm="rmsnorm", positions="rope", attention="latent",
                       ffn="routed_experts", head="untied", eps=1e-6,
                       latent_width=24, top_k=2, dense_layers=1).validate()
    assert routed.routed_layers(4) == (1, 2, 3)


@pytest.mark.parametrize("module", [
    "deepspeed_tpu", "deepspeed_tpu.serving", "deepspeed_tpu.models"])
def test_the_family_is_imported_only_when_it_is_built(module):
    """Nothing of this family at import of the package, of serving or of
    the model zoo: another cell's set-up pays nothing for it."""
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "bad = [m for m in sys.modules if 'nemotron' in m]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})


# the StableHLO of the toy programs of tests/test_program_scopes.py,
# hashed on the commit before this family arrived (PR 60's tree): groups
# in `ssm_mix` and the expert's form in moe/dropless.py are branches
# another family does not take
PINNED = {("granite_hybrid", "prefill"): "62c2ca7083420952",
          ("granite_hybrid", "decode"): "6903ca5f8cceb30d",
          ("deepseek_v2", "prefill"): "6baae40229fcb90d",
          ("deepseek_v2", "decode"): "69f26c931fb7be65",
          ("command_a", "prefill"): "4cdb4f7b89e5e268",
          ("command_a", "decode"): "fe47984e422c93be"}


@pytest.mark.parametrize("family", ["granite_hybrid", "deepseek_v2",
                                    "command_a"])
def test_one_group_and_three_matrices_lower_as_they_did(family):
    """Granite's programs (one group of B and C, the norm over all d_in)
    and two routed families' (SiLU-gated experts through the masked way;
    the grouped way, `grouped_ffn`, now takes both front products before
    the activation, as the masked way and both kernels always did) are,
    operation for operation, what they were."""
    import hashlib

    from test_program_scopes import FAMILIES

    model, serve = FAMILIES[family]()
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(**serve))
    try:
        calls = engine._program_calls()
        for name in ("prefill", "decode"):
            program, args = calls[name]
            text = program.lower(*args).as_text()
            assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
                PINNED[family, name], (family, name)
    finally:
        engine.close()
