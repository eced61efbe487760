"""DeepSeek-V2 through the serving engine: the latent K/V row (MLA) with
its expanded and absorbed paths, the dropless routed FFN, and what is
refused for them — against the plain reference
(`benchmarks/reference/deepseek_v2.py`) on seeded weights at toy widths.
"""

import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v2 as ref
from deepspeed_tpu.models import DeepSeekV2, DeepSeekV2Config, LayerSpec
from deepspeed_tpu.models import deepseek_v2 as dsv2
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (PagedKVCache, ServeConfig, ServeEngine,
                                   ServeProgramBuilder)
from deepspeed_tpu.serving.kv_cache import TRASH_BLOCK, pool_width
from toy_plans import toy_plan

VOCAB, HEADS, NOPE, ROPE, VDIM, RANK, TOPK, EXPERTS = 128, 4, 16, 16, 16, 32, \
    3, 8
YARN = dsv2.Yarn(40.0, 64, 32.0, 1.0, 0.707, 0.707)
KW = dict(heads=HEADS, nope=NOPE, rope=ROPE, v_dim=VDIM, rank=RANK,
          top_k=TOPK, eps=1e-6, theta=10000.0,
          yarn=tuple(sorted(YARN._asdict().items())))


def _config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=128, num_layers=3,
                num_heads=HEADS, d_model=64, kv_lora_rank=RANK,
                qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
                v_head_dim=VDIM, d_ff=96, first_k_dense=1,
                num_experts=EXPERTS, top_k=TOPK, num_shared_experts=1,
                d_expert=48, yarn=YARN, init_std=0.2, router_std=1.0)
    base.update(kw)
    return DeepSeekV2Config(**base)


def _serve(**kw):
    base = dict(block_size=8, num_blocks=40, max_batch=3, prefill_chunk=16,
                max_seq_len=128, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


def _model(dtype=jnp.float32, **kw):
    model = DeepSeekV2(_config(param_dtype=dtype, **kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


# float32: the largest difference.  bf16 (inputs of every product rounded
# to 8 bits of mantissa, through three layers of width 64, on logits of
# standard deviation 1.6): the mean difference — where rounding moves a
# token's third and fourth expert across each other, one of eight experts
# changes at that position and single logits move by several tenths
TOL = {"float32": 2e-4, "bfloat16": 0.06}


def _differ(got, want, dtype):
    d = np.abs(np.asarray(got, np.float32) - want)
    return d.max() if dtype == "float32" else d.mean()


# -- the uncached forward against the reference -------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_plain_reference(dtype):
    model, params = _model(jnp.dtype(dtype))
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, VOCAB, (2, 40)))
    want = np.asarray(ref.logits(params, tokens, **KW))
    got = np.asarray(model.apply(params, tokens))
    assert want.std() > 1.0
    assert _differ(got, want, dtype) < TOL[dtype]


def test_reference_is_independent_of_the_model_under_test():
    import inspect

    src = inspect.getsource(ref)
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    assert 'HIGHEST = "highest"' in src


def test_absorbed_path_equals_expanded_path_in_float32():
    """One layer's attention, both ways, on the same rows: equal in
    exact arithmetic, here to 1e-5 on outputs of order 1; and the whole
    forward through either."""
    model, params = _model()
    cfg, p = model.config, params["blocks"][1]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    q_nope, q_rope, rows = dsv2.latent_project(cfg, p, h, pos, jnp.float32)
    mask = jnp.broadcast_to(jnp.arange(24)[None] <= jnp.arange(24)[:, None],
                            (2, 24, 24))
    a = np.asarray(dsv2.attend_expanded(cfg, p["kv_b"], q_nope, q_rope, rows,
                                        mask))
    b = np.asarray(dsv2.attend_absorbed(cfg, p["kv_b"], q_nope, q_rope, rows,
                                        mask))
    assert a.std() > 0.3 and np.abs(a - b).max() <= 1e-5
    tokens = jnp.asarray([_prompt(30, 5)])
    full = np.asarray(model.apply(params, tokens))
    np.testing.assert_allclose(
        np.asarray(model.apply(params, tokens, absorbed=True)), full,
        atol=1e-4)


def test_which_path_a_call_takes_follows_its_query_count():
    cfg = DeepSeekV2Config()               # the published widths
    assert dsv2.absorb(cfg, 1, 4096)       # decode: one query a slot
    assert not dsv2.absorb(cfg, 256, 4096)  # every prefill chunk offered
    assert not dsv2.absorb(cfg, 1024, 4096)
    # the crossing: expanding 4,096 rows costs 512 x 16 x 256 a row,
    # absorbing makes each (query, head, row) 1,088 wide instead of 320
    assert dsv2.absorb(cfg, 160, 4096) and not dsv2.absorb(cfg, 180, 4096)


# -- YaRN ----------------------------------------------------------------------


def test_yarn_frequencies_and_softmax_factor_by_hand():
    lite = dsv2.Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    # m(0.707) = 0.1 * 0.707 * ln 40 + 1
    assert dsv2.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert dsv2.softmax_scale(192, lite) == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    assert dsv2.softmax_scale(192, None) == pytest.approx(192 ** -0.5)
    inv = np.asarray(dsv2.yarn_inv_freq(64, 10000.0, lite))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 ->
    # 10, and with 1 turn 22.51 -> 23: dims up to 10 keep their frequency,
    # dims from 23 on are divided by 40, between them a linear ramp
    assert math.floor(64 * math.log(4096 / (64 * math.pi))
                      / (2 * math.log(10000))) == 10
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    r = (16 - 10) / 13
    assert inv[16] == pytest.approx(plain[16] * (1 - r) + plain[16] / 40 * r,
                                    rel=1e-6)
    np.testing.assert_allclose(
        inv, np.asarray(ref.yarn_inv_freq(64, 10000.0, lite._asdict())),
        rtol=1e-6)
    # cos and sin carry m(mscale) / m(mscale_all_dim) = 1 here: a rotation
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 64))
    y = dsv2.rope_part(x, jnp.arange(5)[None] * 1000, 10000.0, lite)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


# -- the dropless routed FFN ---------------------------------------------------


def _experts(key, e=EXPERTS, d=64, f=48, dtype=jnp.float32):
    k = jax.random.split(key, 3)
    return {"gate": (jax.random.normal(k[0], (e, d, f)) * 0.2).astype(dtype),
            "up": (jax.random.normal(k[1], (e, d, f)) * 0.2).astype(dtype),
            "down": (jax.random.normal(k[2], (e, f, d)) * 0.2).astype(dtype)}


def _one_by_one(x, experts, weights, idx):
    """Every assignment computed exactly once, in a Python loop."""
    x, out = np.asarray(x, np.float64), np.zeros(x.shape, np.float64)
    g, u, d = (np.asarray(experts[n], np.float64)
               for n in ("gate", "up", "down"))
    for t in range(x.shape[0]):
        for w, e in zip(np.asarray(weights[t]), np.asarray(idx[t])):
            a = x[t] @ g[e]
            out[t] += w * ((a / (1 + np.exp(-a)) * (x[t] @ u[e])) @ d[e])
    return out


SCENES = {
    "batch_1": lambda: (1, None),
    # every token's first choice is expert 5
    "one_expert_takes_every_token": lambda: (12, 5),
    "ordinary": lambda: (12, None),
}


@pytest.mark.parametrize("way", ["masked", "grouped"])
@pytest.mark.parametrize("scene", list(SCENES))
def test_every_assignment_is_computed_exactly_once(scene, way):
    n, hot = SCENES[scene]()
    x = jax.random.normal(jax.random.PRNGKey(7), (n, 64))
    router = jax.random.normal(jax.random.PRNGKey(8), (64, EXPERTS)) * 0.1
    if hot is not None:
        # a router that sends everything to `hot` first and never to
        # expert 2: one full expert, one empty one
        x = x.at[:, 0].set(30.0)
        router = router.at[0].set(0.0).at[0, hot].set(0.2).at[0, 2].set(-0.2)
    weights, idx = dropless.route(x, router, TOPK)
    if hot is not None:
        assert (np.asarray(idx[:, 0]) == hot).all()
        assert 2 not in np.asarray(idx)
    assert idx.shape == (n, TOPK) and len(set(np.asarray(idx[0]))) == TOPK
    # unrenormalised softmax weights: they sum to less than 1
    assert (np.asarray(weights.sum(-1)) < 1.0).all()
    experts = _experts(jax.random.PRNGKey(9))
    fn = {"masked": dropless.experts_masked,
          "grouped": dropless.experts_grouped}[way]
    got = np.asarray(jax.jit(fn)(x, experts, weights, idx))
    want = _one_by_one(x, experts, weights, idx)
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_the_way_is_chosen_from_the_calls_shapes():
    picked = []
    real = dropless.experts_masked, dropless.experts_grouped
    try:
        dropless.experts_masked = lambda *a: picked.append("masked")
        dropless.experts_grouped = lambda *a: picked.append("grouped")
        ex = dict.fromkeys(("gate", "up"), jnp.zeros((64, 8, 4)))
        for tokens in (1, 10, 11, 32, 128, 129, 512):
            dropless.routed_experts(None, ex, None,
                                    jnp.zeros((tokens, 6), jnp.int32))
    finally:
        dropless.experts_masked, dropless.experts_grouped = real
    # 6 of 64 experts a token: 11 tokens' assignments can cover them;
    # past 128 tokens the masked products cost more than the bytes
    assert picked == ["grouped", "grouped", "masked", "masked", "masked",
                      "grouped", "grouped"]


def test_on_a_tpu_every_call_under_the_ridge_follows_the_touched_list(
        native):
    """The chip-side half of the chooser, at the `chatgen` cell's expert
    shapes: whatever its assignments cover, a call of up to 128 rows
    reads only the experts its live rows touched; a prefill chunk walks
    its rows as one slab (PR 58); `DS_KERNEL_TOUCHED_EXPERTS=0` gives
    the two old ways back under the ridge."""
    ex = dict.fromkeys(("gate", "up"), jax.ShapeDtypeStruct((64, 2048, 1408), jnp.bfloat16))
    ways = lambda: [dropless.routed_way(t, 6, ex)
                    for t in (1, 10, 11, 32, 128, 129, 512)]
    assert ways() == ["touched"] * 5 + ["slabs"] * 2
    picked = []
    real = dropless.experts_touched_only
    try:
        dropless.experts_touched_only = \
            lambda *a: picked.append([None if v is None else v.shape
                                      for v in a[3:]])
        dropless.routed_experts(None, ex, None, jnp.zeros((32, 6), jnp.int32),
                                live=jnp.ones((32,), bool))
    finally:
        dropless.experts_touched_only = real
    assert picked == [[(32, 6), (32,), None]]     # idx, live, held
    import os
    os.environ["DS_KERNEL_TOUCHED_EXPERTS"] = "0"
    try:
        assert ways() == ["grouped", "grouped", "masked", "masked",
                          "masked", "slabs", "slabs"]
        os.environ["DS_KERNEL_GROUPED_EXPERTS"] = "0"
        assert ways()[-2:] == ["grouped", "grouped"]
    finally:
        del os.environ["DS_KERNEL_TOUCHED_EXPERTS"]
        os.environ.pop("DS_KERNEL_GROUPED_EXPERTS", None)


def test_routed_ffn_equals_the_all_experts_masked_reference():
    model, params = _model()
    p = params["blocks"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 20, 64))
    idx = dsv2.expert_ffn(model.config, p["mlp"], h)[1]
    ones = jnp.ones((64,))
    want = ref._expert_ffn(h, p["mlp"], ones, top_k=TOPK, eps=0.0) - h
    # the reference norms its input: feed both the same normed rows
    hn = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True))
    y = dsv2.expert_ffn(model.config, p["mlp"], hn)[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-4)
    assert idx.shape == (40, TOPK)


def test_experts_touched_counts_live_tokens_only():
    idx = jnp.asarray([[0, 1, 2], [2, 3, 4], [7, 6, 5]], jnp.int32)
    count = lambda live: int(dropless.experts_touched(
        idx, jnp.asarray(live), EXPERTS))
    assert count([True, True, True]) == 8
    assert count([True, True, False]) == 5
    assert count([False, False, False]) == 0


# -- through the engine --------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_matches_the_reference_forward(dtype):
    """Five requests of ragged lengths through three slots (a slot is
    reused, a prompt spans three prefill chunks): at every generated
    position the logits the engine drew from are the reference's full
    forward's, and the token is its argmax (float32) or within the
    rounding of it (bf16)."""
    from test_evabyte import Probe

    model, params = _model(jnp.dtype(dtype))
    probe = Probe(model, params, _serve())
    eng = probe.engine
    reqs = [eng.submit(_prompt(n, i), 10)
            for i, n in enumerate([5, 17, 40, 20, 9])]
    probe.run()
    assert [r.state for r in reqs] == ["finished"] * 5
    assert eng.kv.blocks_in_use == 0
    for r in reqs:
        lg = np.asarray(ref.logits(
            params, jnp.asarray([r.prompt + r.out]), **KW))[0]
        first = len(r.prompt) - 1
        want = lg[first:first + len(r.out)]
        got = np.stack(probe.logits[r.rid])[:len(r.out)]
        assert _differ(got, want, dtype) < TOL[dtype], r.rid
        chosen = want[np.arange(len(r.out)), r.out]
        assert (want.max(-1) - chosen).mean() <= (0 if dtype == "float32"
                                                  else TOL[dtype])


@pytest.mark.parametrize("change", [
    dict(scoring="sigmoid"), dict(renormalize=True), dict(experts_held=2),
    dict(select_bias=True), dict(route_scale=2.5)])
def test_the_sequential_blocks_routed_ffn_reads_the_spec(change, monkeypatch):
    """One routed FFN for both blocks (models/cohere2_moe.py
    `expert_ffn`): with a field of the spec changed, the engine's logits
    are the uncached forward's under that spec, and not the plain
    spec's."""
    from deepspeed_tpu.models import cohere2_moe
    from test_evabyte import Probe

    class Respecced(DeepSeekV2):
        def layer_spec(self):
            return super().layer_spec()._replace(**change).validate()

    model = Respecced(_config())
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    for i, bp in enumerate(params["blocks"][model.config.first_k_dense:]):
        bp["mlp"]["select_bias"] = jax.random.normal(
            jax.random.PRNGKey(i), (EXPERTS,)) * 0.5
    probe = Probe(model, params, _serve())
    req = probe.engine.submit(_prompt(21, 4), 6)
    probe.run()
    got = np.stack(probe.logits[req.rid])[:len(req.out)]
    toks, first = jnp.asarray([req.prompt + req.out]), len(req.prompt) - 1
    plain = np.asarray(model.apply(params, toks))[0, first:first + len(got)]
    spec = model.layer_spec()
    monkeypatch.setattr(dsv2, "expert_ffn", lambda c, p, h:
                        cohere2_moe.expert_ffn(spec, c, p, h))
    want = np.asarray(model.apply(params, toks))[0, first:first + len(got)]
    assert np.abs(got - want).max() < TOL["float32"]
    assert np.abs(got - plain).max() > 1e-2


def test_a_request_decodes_the_same_alone_and_in_a_batch():
    model, params = _model()
    alone = ServeEngine(model, params, _serve()).generate([_prompt(19, 3)],
                                                          12)[0]
    eng = ServeEngine(model, params, _serve())
    outs = eng.generate([_prompt(7, 1), _prompt(19, 3), _prompt(33, 2)], 12)
    assert outs[1] == alone


@pytest.mark.parametrize("way", ["oracle", "kernel"])
def test_rows_walked_is_what_a_decode_step_fetches(way, chip_rule):
    """`serve.mla.rows_walked` beside `serve.mla.rows_read`: where the
    registry answers the oracle for the decode program's shapes (every
    backend but the TPU) a step gathers the table's whole width for
    every running slot; where it answers the walk, a slot's cached
    length rounded up to a block — in decode's layers and no other call
    (a prefill chunk keeps the gather), and the tokens served are the
    oracle's."""
    import contextlib

    model, params = _model()
    lengths, bs = (8, 21), 8
    prompts = [_prompt(n, i + 1) for i, n in enumerate(lengths)]
    with chip_rule("latent_attention") if way == "kernel" \
            else contextlib.nullcontext():
        eng = ServeEngine(model, params, _serve())
        before = COUNTERS.snapshot()
        out = eng.generate(prompts, 6)
    d = COUNTERS.delta_since(before)
    assert eng._counted["latent"].walks == ((way == "kernel", None),)
    held = [n + i + 1 for n in lengths for i in range(5)]
    assert d["serve.mla.rows_read"] == {"calls": 10, "bytes": sum(held)}
    fetched = sum(-(-h // bs) * bs for h in held) if way == "kernel" \
        else 10 * eng.kv.table_width * bs
    assert d["serve.mla.rows_walked"] == {"calls": 10, "bytes": fetched}
    if way == "kernel":
        # within a block a query of the rows attended
        assert sum(held) <= fetched <= sum(held) + 10 * (bs - 1)
        assert d["kernel.dispatches"]["calls"] == model.config.num_layers
        assert out == ServeEngine(model, params, _serve()).generate(prompts,
                                                                   6)
    else:
        assert "kernel.dispatches" not in d


def test_counters_of_a_decode_step():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    before = COUNTERS.snapshot()
    eng.generate([_prompt(8, 1), _prompt(21, 2)], 6)
    d = COUNTERS.delta_since(before)
    # 2 requests x 5 decode steps (the first token comes from prefill),
    # a query at position p attends p + 1 rows
    rows = sum(n + i + 1 for n in (8, 21) for i in range(5))
    assert d["serve.mla.rows_read"] == {"calls": 10, "bytes": rows}
    # 2 routed layers; 29 prompt tokens and 10 decoded ones, top 3
    assert d["serve.moe.assignments"]["bytes"] == (29 + 10) * TOPK * 2
    touched = d["serve.moe.experts_touched"]
    # the second prompt is two chunks long, so the first request decodes
    # alone for a while: more steps than tokens a request
    steps = d["serve.decode_steps"]["calls"]
    assert steps > 5 and touched["calls"] == steps * 2
    # one or two live tokens a step choose 3..6 different experts a layer
    assert steps * 2 * 3 <= touched["bytes"] <= steps * 2 * 6
    # off a TPU 3 slots x top 3 cover the 8 experts: the masked way,
    # which reads every expert of both layers whatever was touched
    assert d["serve.moe.experts_streamed"] == {
        "calls": steps * 2, "bytes": steps * 2 * EXPERTS}


@pytest.mark.parametrize("slots,way", [(1, "grouped"), (3, "masked")])
def test_experts_streamed_is_what_the_chosen_way_reads(slots, way):
    """`serve.moe.experts_streamed` against `experts_touched`: equal
    where the step's routed product follows what was touched (here the
    grouped way; on a TPU the touched list), the experts held where it
    masks — from the number already read back, or a constant."""
    model, params = _model()
    experts = params["blocks"][1]["mlp"]["experts"]
    assert dropless.routed_way(slots, TOPK, experts) == way
    eng = ServeEngine(model, params, _serve(max_batch=slots))
    before = COUNTERS.snapshot()
    eng.generate([_prompt(8, 1)], 4)
    d = COUNTERS.delta_since(before)
    touched = d["serve.moe.experts_touched"]
    streamed = d["serve.moe.experts_streamed"]
    assert streamed["calls"] == touched["calls"] == 3 * 2
    assert streamed["bytes"] == (touched["bytes"] if way == "grouped"
                                 else 3 * 2 * EXPERTS)
    assert touched["bytes"] == 3 * 2 * TOPK      # one live token a step


def test_experts_touched_is_read_one_step_late_and_loses_nothing():
    """The entry behind a step's tokens reaches the host with them, one
    step after the launch: when step k is launched the counter holds
    steps 1..k-2, and at the end it holds every step's entry."""
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    decode, R, layers = eng.programs["decode"], 3, 2
    name = "serve.moe.experts_touched"
    launched, counted = [], []

    def recording(*args):
        out = decode(*args)
        launched.append(out[0])
        counted.append(COUNTERS.totals().get(name, {"calls": 0})["calls"])
        return out

    eng.programs = dict(eng.programs, decode=recording)
    before = COUNTERS.snapshot()
    base = COUNTERS.totals().get(name, {"calls": 0})["calls"]
    reqs = [eng.submit(_prompt(8, 1), 9), eng.submit(_prompt(21, 2), 4)]
    eng.run()
    d = COUNTERS.delta_since(before)
    assert [len(r.out) for r in reqs] == [9, 4] and len(launched) >= 8
    assert counted == [base + max(k - 1, 0) * layers
                       for k in range(len(launched))]
    assert d[name] == {"calls": len(launched) * layers,
                       "bytes": sum(int(o[R]) for o in launched)}
    assert d["serve.decode_ahead"] == {"calls": len(launched),
                                       "bytes": len(launched) - 1}


def test_decode_appends_experts_touched_to_its_tokens():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    R, W = 3, eng.kv.table_width
    active = jnp.asarray([True, False, False])
    out, _, (toks, moved) = eng.programs["decode"](
        eng.params, eng.kv.caches, jnp.zeros((R,), jnp.int32),
        jnp.zeros((R,), jnp.int32), active, jnp.zeros((R, W), jnp.int32),
        jnp.zeros((R,), jnp.float32), jnp.zeros((R,), jnp.int32),
        jnp.zeros((R,), jnp.uint32))
    # one live token: top 3 experts in each of the 2 routed layers
    assert out.shape == (R + 1,) and int(out[R]) == 2 * TOPK
    # what the next step takes: the samples alone, the live slot moved on
    assert np.array_equal(toks, out[:R]) and moved.tolist() == [1, 0, 0]


# -- the row under the one allocator -------------------------------------------


def test_latent_rows_under_the_one_allocator():
    assert pool_width(1, 576) == 640 and pool_width(1, 48) == 128
    kv = PagedKVCache(toy_plan(3, 4, 32, 8, 32, attention="latent",
                               latent_width=576), 9, dtype=jnp.bfloat16,
                      prefix_cache=False)
    assert kv.table_width == 4
    assert len(kv.caches) == 3 and all(len(e) == 1 for e in kv.caches)
    assert kv.caches[0][0].shape == (72, 640)
    assert kv.nbytes() == 3 * 72 * 640 * 2
    a = kv.alloc("a", 3)
    b = kv.alloc("b", 4)
    assert kv.alloc("c", 2) is None          # 8 allocatable, 7 held
    assert TRASH_BLOCK not in set(a[:3]) | set(b[:4])
    assert (a[3:] == TRASH_BLOCK).all()
    kv.free("a")
    c = kv.alloc("c", 3)
    assert set(c[:3]) == set(a[:3])          # freed blocks are reused
    kv.free("b"), kv.free("c")
    assert kv.blocks_in_use == 0 and kv.free_blocks == 8


def test_latent_cache_refuses_what_it_cannot_hold():
    plan = toy_plan(1, 4, 32, 8, 32, attention="latent", latent_width=48)
    mesh = types.SimpleNamespace(size=2, axis_size=lambda axis: 1)
    for kw in ({"dtype": "int8"}, {"prefix_cache": True},
               {"mesh_info": mesh}):
        base = dict(dtype=jnp.bfloat16, prefix_cache=False)
        base.update(kw)
        with pytest.raises(ValueError, match="latent rows"):
            PagedKVCache(plan, 9, **base)


def test_engine_leaves_the_trash_block_and_freed_blocks_alone():
    """Rows land only in the blocks a request holds: after a run the
    trash block is the only block inactive slots wrote, and a second
    wave through reused blocks decodes what it decodes alone."""
    model, params = _model()
    eng = ServeEngine(model, params, _serve(num_blocks=12))
    first = eng.generate([_prompt(20, 1), _prompt(30, 2)], 8)
    again = eng.generate([_prompt(20, 1), _prompt(30, 2)], 8)
    assert first == again and eng.kv.blocks_in_use == 0


# -- refused, by name ----------------------------------------------------------


@pytest.mark.parametrize("serve,match", [
    (dict(prefix_cache=True), "prefix_cache=True over latent rows"),
    (dict(draft_len=2), "draft_len > 0 over latent rows"),
    (dict(kv_dtype="int8"), "kv_dtype 'int8' over latent rows"),
    (dict(kv_dtype="int4"), "kv_dtype 'int4' over latent rows"),
    (dict(quantized_weights="int8"), "quantized_weights over latent rows"),
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
                       match="sessions over latent rows"):
        eng.submit(_prompt(5), 4, session_id="s")
    with pytest.raises(NotImplementedError,
                       match="a mesh of 2 devices over latent rows"):
        ServeEngine(model, params, _serve(),
                    mesh_info=make_mesh(model=2, data=1,
                                        devices=jax.devices()[:2]))


# -- the layer spec -------------------------------------------------------------


def test_layer_spec_of_the_new_kinds():
    model, _ = _model()
    spec = model.layer_spec()
    assert (spec.norm, spec.positions, spec.attention, spec.ffn, spec.head) \
        == ("rmsnorm", "rope", "latent", "routed_experts", "untied")
    assert (spec.latent_width, spec.top_k, spec.dense_layers) == (48, 3, 1)
    builder = ServeProgramBuilder(model, ServeEngine(
        model, model.init(jax.random.PRNGKey(0)),
        _serve()).programs["schedule"])
    assert builder.spec == spec


@pytest.mark.parametrize("change,match", [
    (dict(latent_width=0), "latent_width"),
    (dict(attention="paged"), "latent_width"),
    (dict(top_k=0), "top_k"),
    (dict(ffn="silu_gated"), "top_k"),
    (dict(ffn="silu_gated", top_k=0), "dense_layers"),
    (dict(norm="rmsnorm_plain"), "is not one of"),
])
def test_layer_spec_validate_refuses(change, match):
    good = LayerSpec(norm="rmsnorm", positions="rope", attention="latent",
                     ffn="routed_experts", head="untied", eps=1e-6,
                     rope_theta=1e4, latent_width=48, top_k=3,
                     dense_layers=1).validate()
    with pytest.raises(ValueError, match=match):
        good._replace(**change).validate()
