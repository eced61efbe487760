"""Command A+ through the serving engine: sliding layers with rotary
positions beside full layers without, grouped K/V rows, two groups of
layers with a ring, the parallel block, sigmoid routing and a share of
the experts — against the plain reference
(`benchmarks/reference/cohere2_moe.py`) on seeded weights at toy widths:
8 layers (two periods), hidden 64, 8 query heads on 2 K/V heads of 16,
window 32, 8 experts top 4 of width 32, 2 shared, vocabulary 128.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import cohere2_moe as ref
from deepspeed_tpu.kernels import registry
from deepspeed_tpu.models import Cohere2Moe, Cohere2MoeConfig, LayerSpec
from deepspeed_tpu.models import cohere2_moe as c2
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (PagedKVCache, ServeConfig, ServeEngine,
                                   ServeProgramBuilder, ServeSchedule)
from deepspeed_tpu.serving import layers as serving_layers
from deepspeed_tpu.serving.kv_cache import (TRASH_BLOCK, cache_plan,
                                            pool_width)
from toy_plans import toy_plan

VOCAB, LAYERS, HEADS, KV, DH, WINDOW, EXPERTS, TOPK, SHARED = \
    128, 8, 8, 2, 16, 32, 8, 4, 2
BS, CHUNK = 8, 16
RING = WINDOW + CHUNK


def _config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=256, num_layers=LAYERS,
                num_heads=HEADS, kv_heads=KV, head_dim=DH, d_model=64,
                d_expert=32, num_experts=EXPERTS, top_k=TOPK,
                num_shared=SHARED, window=WINDOW, init_std=0.2)
    base.update(kw)
    return Cohere2MoeConfig(**base)


def _kw(cfg):
    return dict(heads=cfg.num_heads, kv_heads=cfg.kv_heads, top_k=cfg.top_k,
                shared=cfg.num_shared, first_expert=cfg.first_expert,
                windows=tuple(cfg.window_of(i)
                              for i in range(cfg.num_layers)),
                eps=cfg.layer_norm_eps, theta=cfg.rope_theta)


def _serve(**kw):
    base = dict(block_size=BS, num_blocks=120, max_batch=3,
                prefill_chunk=CHUNK, max_seq_len=256, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


def _model(dtype=jnp.float32, **kw):
    model = Cohere2Moe(_config(param_dtype=dtype, **kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


# float32: the largest difference.  bf16 (inputs of every product rounded
# to 8 bits of mantissa, through eight layers of width 64, on logits of
# standard deviation 1.5): the mean difference — where rounding moves a
# token's fourth and fifth expert across each other single logits move by
# several tenths (0.05-0.09 a request through the engine, whose cache
# rows are rounded too)
TOL = {"float32": 3e-4, "bfloat16": 0.12}


def _differ(got, want, dtype):
    d = np.abs(np.asarray(got, np.float32) - want)
    return d.max() if dtype == "float32" else d.mean()


# -- the uncached forward against the reference -------------------------------


@pytest.mark.parametrize("held", [None, (2, 4)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_plain_reference(dtype, held):
    share = {} if held is None else dict(first_expert=held[0],
                                         experts_held=held[1])
    model, params = _model(jnp.dtype(dtype), **share)
    assert params["blocks"][0]["mlp"]["experts"]["gate"].shape[0] == \
        (EXPERTS if held is None else held[1])
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, VOCAB, (2, 5 * WINDOW)))
    want = np.asarray(ref.logits(params, tokens, **_kw(model.config)))
    got = np.asarray(jax.jit(model.apply)(params, tokens))
    assert want.std() > 1.0
    assert _differ(got, want, dtype) < TOL[dtype]


def test_reference_is_independent_of_the_model_under_test():
    import inspect

    src = inspect.getsource(ref)
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    assert 'HIGHEST = "highest"' in src


def test_reference_blocks_change_nothing(monkeypatch):
    """Queries, tokens and vocabulary in blocks of any size: the same
    logits to float32 rounding."""
    model, params = _model()
    tokens = jnp.asarray([_prompt(48, 3)])
    want = np.asarray(ref.logits(params, tokens, **_kw(model.config)))
    for name, size in (("QUERY_BLOCK", 4), ("TOKEN_BLOCK", 6),
                       ("HEAD_BLOCK", 32)):
        monkeypatch.setattr(ref, name, size)
    jax.clear_caches()
    got = np.asarray(ref.logits(params, tokens, **_kw(model.config)))
    jax.clear_caches()
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- positions, rows, routing: by hand ------------------------------------------


def test_interleaved_rope_by_hand():
    """Dims 2i and 2i + 1 turn together by p theta^(-2i/dh): head size 4
    at position 3, theta 100 -> angles 3 and 0.3."""
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 1, 4)
    got = np.asarray(c2.rope_interleaved(x, jnp.asarray([[3]]), 100.0))[0, 0, 0]
    a, b = 3.0, 3.0 * 100.0 ** -0.5
    want = [np.cos(a) - 2 * np.sin(a), 2 * np.cos(a) + np.sin(a),
            3 * np.cos(b) - 4 * np.sin(b), 4 * np.cos(b) + 3 * np.sin(b)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the half-split pairing (EvaByte's, DeepSeek's) is another rotation
    from deepspeed_tpu.models.evabyte import rope

    assert np.abs(np.asarray(rope(x, jnp.asarray([[3]]), 100.0))[0, 0, 0]
                  - want).max() > 0.5
    # and the reference's own
    np.testing.assert_allclose(
        np.asarray(ref._rope_gptj(x[0], jnp.asarray([3]), 100.0))[0, 0],
        want, rtol=1e-6)


def test_grouped_rows_and_which_head_reads_which():
    assert pool_width(2, 16) == 128 and pool_width(8, 128) == 1024
    # 4 query heads on 2 K/V heads: K/V head 0 has the key, K/V head 1
    # only zeros, so query heads 0, 1 see the value and 2, 3 do not
    q = jnp.ones((1, 1, 4, 4))
    k = jnp.zeros((1, 2, 2, 4)).at[0, 1, 0].set(10.0)
    v = jnp.zeros((1, 2, 2, 4)).at[0, 1, 0].set(1.0).at[0, 1, 1].set(-1.0)
    out = np.asarray(c2.attend_grouped(
        q, k, v, jnp.ones((1, 1, 2), bool))).reshape(4, 4)
    np.testing.assert_allclose(out[:2], 1.0, atol=1e-6)   # n // 2 == 0
    np.testing.assert_allclose(out[2:], -0.5, atol=1e-6)  # n // 2 == 1


def test_attention_a_kv_head_at_a_time_is_the_same(monkeypatch):
    key = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(key[0], (2, 6, HEADS, DH))
    k = jax.random.normal(key[1], (2, 20, KV, DH))
    v = jax.random.normal(key[2], (2, 20, KV, DH))
    mask = jnp.arange(20)[None, None, :] <= jnp.arange(6)[None, :, None] + 14
    mask = jnp.broadcast_to(mask, (2, 6, 20))
    once = np.asarray(c2.attend_grouped(q, k, v, mask))
    monkeypatch.setattr(c2, "SCORE_BYTES", 0)
    np.testing.assert_allclose(
        np.asarray(c2.attend_grouped(q, k, v, mask)), once, atol=1e-6)


def test_sigmoid_top_k_renormalised_by_hand():
    # h = e_0, so the router's logits are its first row
    logits = np.asarray([2.0, -1.0, 0.5, 3.0, 0.0, -2.0])
    router = jnp.zeros((4, 6)).at[0].set(logits)
    h = jnp.asarray([[1.0, 0.0, 0.0, 0.0]])
    s = 1 / (1 + np.exp(-logits))
    w, idx = dropless.route(h, router, 3, scoring="sigmoid",
                            renormalize=True)
    assert idx.tolist() == [[3, 0, 2]]
    np.testing.assert_allclose(np.asarray(w)[0],
                               s[[3, 0, 2]] / s[[3, 0, 2]].sum(), rtol=1e-6)
    raw, _ = dropless.route(h, router, 3, scoring="sigmoid")
    np.testing.assert_allclose(np.asarray(raw)[0], s[[3, 0, 2]], rtol=1e-6)
    # the softmax router of the other family is untouched by the option
    soft, idx = dropless.route(h, router, 3)
    e = np.exp(logits - logits.max())
    np.testing.assert_allclose(np.asarray(soft)[0], (e / e.sum())[[3, 0, 2]],
                               rtol=1e-6)


def test_ring_position_arithmetic():
    """The position ring row j holds: the largest p <= newest with
    p % ring = j — at newest = ring - 1 (no wrap yet), ring (row 0
    rewritten) and ring + chunk."""
    ring = RING

    def held(newest):
        j = jnp.arange(ring)
        return np.asarray(newest - (newest - j) % ring)

    assert held(ring - 1).tolist() == list(range(ring))
    assert held(ring).tolist() == [ring] + list(range(1, ring))
    wrapped = held(ring + CHUNK)
    assert wrapped[:CHUNK + 1].tolist() == list(range(ring, ring + CHUNK + 1))
    assert wrapped[CHUNK + 1:].tolist() == list(range(CHUNK + 1, ring))
    # before the ring is full the rows past the newest hold nothing
    assert (held(5)[6:] < 0).all() and held(5)[:6].tolist() == list(range(6))


# -- a share of the experts ------------------------------------------------------


def _experts(key, e, d=64, f=32):
    k = jax.random.split(key, 3)
    return {"gate": jax.random.normal(k[0], (e, d, f)) * 0.2,
            "up": jax.random.normal(k[1], (e, d, f)) * 0.2,
            "down": jax.random.normal(k[2], (e, f, d)) * 0.2}


def _share(experts, first, count):
    return {n: w[first:first + count] for n, w in experts.items()}


SCENES = {
    # (tokens, the held share, the router's first row)
    "a_held_subset": (12, (2, 4), None),
    # the router never sends anything to expert 3: held and empty
    "an_empty_held_expert": (12, (2, 4), {3: -9.0}),
    # every token's choices lie among experts 0..3: nothing held of them
    "every_assignment_elsewhere": (12, (4, 4),
                                   {0: 9.0, 1: 9.0, 2: 9.0, 3: 9.0}),
    "batch_1": (1, (0, 2), None),
}


@pytest.mark.parametrize("scene", list(SCENES))
def test_masked_and_grouped_ways_agree_on_a_share(scene):
    n, (first, count), push = SCENES[scene]
    x = jax.random.normal(jax.random.PRNGKey(7), (n, 64))
    router = jax.random.normal(jax.random.PRNGKey(8), (64, EXPERTS)) * 0.1
    if push:
        x = x.at[:, 0].set(30.0)
        router = router.at[0].set(0.0)
        for e, bias in push.items():
            router = router.at[0, e].set(bias)
    weights, idx = dropless.route(x, router, TOPK, scoring="sigmoid",
                                  renormalize=True)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    experts = _experts(jax.random.PRNGKey(9), EXPERTS)
    w, local, held = dropless.held_assignments(weights, idx, first, count)
    if scene == "every_assignment_elsewhere":
        assert not np.asarray(held).any()
    if scene == "an_empty_held_expert":
        assert 3 not in np.asarray(idx)
    mine = _share(experts, first, count)
    masked = np.asarray(jax.jit(dropless.experts_masked)(x, mine, w, local))
    grouped = np.asarray(jax.jit(dropless.experts_grouped)(
        x, mine, w, local, held))
    # every expert on every token, the weights of those elsewhere dropped
    dense = jnp.zeros((n, EXPERTS)).at[jnp.arange(n)[:, None], idx].add(
        weights)[:, first:first + count]
    want = np.asarray(dropless.experts_masked(
        x, mine, dense, jnp.broadcast_to(jnp.arange(count), (n, count))))
    tol = 2e-6 * max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(masked, want, atol=tol)
    np.testing.assert_allclose(grouped, want, atol=tol)
    if scene == "every_assignment_elsewhere":
        assert not masked.any() and not grouped.any()


def test_the_way_is_chosen_from_the_assignments_held():
    picked = []
    real = dropless.experts_masked, dropless.experts_grouped
    try:
        dropless.experts_masked = lambda *a: picked.append("masked")
        dropless.experts_grouped = lambda *a: picked.append("grouped")
        ex = dict.fromkeys(("gate", "up"), jnp.zeros((16, 8, 4)))     # 16 of 128 held, top 8
        for tokens in (1, 15, 16, 128, 129, 512):
            dropless.routed_experts(None, ex, None,
                                    jnp.zeros((tokens, 8), jnp.int32),
                                    total=128)
    finally:
        dropless.experts_masked, dropless.experts_grouped = real
    # 16 tokens' 128 assignments leave one for each of the 16 held, as 16
    # tokens' would cover all 128: counted on the 16 held alone (T k >=
    # 16) two tokens would already stream every expert
    assert picked == ["grouped", "grouped", "masked", "masked", "grouped",
                      "grouped"]


def test_on_a_tpu_a_share_under_the_ridge_follows_the_touched_list(native):
    """The chip-side half, at the `mixedlen` cell's expert shapes (16 of
    128 held, 8 a token): any call of up to 128 rows reads the held
    experts its live rows touched — one token's 8 assignments hold one
    expert on average, and none is read for the other 15 — and a
    prefill chunk walks the held rows a slab at a time (PR 58)."""
    ex = dict.fromkeys(("gate", "up"), jax.ShapeDtypeStruct((16, 4096, 4096), jnp.bfloat16))
    assert [dropless.routed_way(t, 8, ex, 128)
            for t in (1, 15, 16, 128, 129, 512)] == \
        ["touched"] * 4 + ["slabs"] * 2
    # widths the kernel cannot tile: the choice off a TPU
    small = dict.fromkeys(("gate", "up"), jax.ShapeDtypeStruct((16, 64, 32), jnp.float32))
    assert [dropless.routed_way(t, 8, small, 128) for t in (15, 16, 129)] \
        == ["grouped", "masked", "grouped"]


def test_the_shares_add_up():
    """The routed parts that the E / held shares give, with the shared
    experts counted once, are what the uncut reference gives for the
    layer's FFN; and so through the model's own routed FFN."""
    model, params = _model()
    cfg, p = model.config, params["blocks"][1]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, 64))
    whole = np.asarray(ref._ffn(h, p, top_k=TOPK, shared=SHARED,
                                first_expert=0))
    shared = np.asarray(ref._ffn(
        h, dict(p, experts=_share(p["experts"], 0, 0)), top_k=TOPK,
        shared=SHARED, first_expert=0))
    count = 2
    parts, mine = [], []
    for first in range(0, EXPERTS, count):
        share = dict(p, experts=_share(p["experts"], first, count))
        parts.append(np.asarray(ref._ffn(
            h, share, top_k=TOPK, shared=SHARED, first_expert=first))
            - shared)
        held = Cohere2MoeConfig(**dict(
            vars(cfg), experts_held=count, first_expert=first))
        mine.append(np.asarray(c2.expert_ffn(
            Cohere2Moe(held).layer_spec(), held, share, h)[0]) - shared)
    assert np.abs(whole - shared).std() > 0.05
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    np.testing.assert_allclose(sum(mine) + shared, whole, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(c2.expert_ffn(model.layer_spec(), cfg, p, h)[0]), whole,
        atol=2e-5)


def test_shared_experts_are_averaged_not_summed():
    model, params = _model()
    p = params["blocks"][0]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(5), (6, 64))
    none = dict(p, experts=_share(p["experts"], 0, 0))
    mean = np.asarray(ref._ffn(h, none, top_k=TOPK, shared=SHARED,
                               first_expert=0))
    width = p["shared"]["gate"].shape[1] // SHARED
    each = [np.asarray(ref._gated(
        h, p["shared"]["gate"][:, i * width:(i + 1) * width],
        p["shared"]["up"][:, i * width:(i + 1) * width],
        p["shared"]["down"][i * width:(i + 1) * width]))
        for i in range(SHARED)]
    np.testing.assert_allclose(mean, sum(each) / SHARED, atol=1e-5)


def test_the_other_familys_routed_output_is_unchanged_bitwise():
    """`route` and `routed_experts` as DeepSeek-V2 calls them — no
    option given — against the expressions they were before this model
    came: softmax, top k as it is, both ways over all the experts."""
    x = jax.random.normal(jax.random.PRNGKey(7), (12, 64))
    router = jax.random.normal(jax.random.PRNGKey(8), (64, EXPERTS))
    experts = _experts(jax.random.PRNGKey(9), EXPERTS)
    scores = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
    w0, i0 = jax.lax.top_k(jax.nn.softmax(scores, axis=-1), 3)
    w, idx = dropless.route(x, router, 3)
    assert np.array_equal(w, w0) and np.array_equal(idx, i0)
    for fn in (dropless.experts_masked, dropless.experts_grouped):
        plain = jax.jit(fn).lower(x, experts, w, idx).as_text()
        via = jax.jit(lambda *a: dropless.routed_experts(*a)).lower(
            x, experts, w, idx).as_text()
        if fn is dropless.experts_masked:       # 12 x 3 >= 8: the masked way
            assert plain.split("{", 1)[1] == via.split("{", 1)[1]
    from deepspeed_tpu.models import DeepSeekV2, DeepSeekV2Config

    m = DeepSeekV2(DeepSeekV2Config(
        vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2, d_model=32,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, d_ff=32, num_experts=4, top_k=2, d_expert=16,
        yarn=None))
    text = jax.jit(m.apply).lower(
        jax.eval_shape(m.init, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((1, 8), jnp.int32)).as_text()
    assert "logistic" not in text.replace("silu", "")  # no sigmoid router


# -- through the engine --------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_matches_the_reference_forward(dtype):
    """Five requests through three slots (a slot is reused), from under
    one block to 5 x the window; one prompt ends a row short of the ring,
    so that its decode steps wrap it, two wrap it in prefill — the ring
    (56 rows) is no multiple of the chunk (24), so a chunk straddles the
    wrap: at every generated position the logits the engine drew from
    are the reference's full forward's."""
    from test_evabyte import Probe

    model, params = _model(jnp.dtype(dtype), first_expert=2, experts_held=4)
    probe = Probe(model, params, _serve(prefill_chunk=24))
    eng = probe.engine
    ring = eng.kv.ring_tokens
    assert ring == WINDOW + 24 and ring % 24
    lengths = [5, 17, ring - 1, 5 * WINDOW, 3 * WINDOW + 5]
    reqs = [eng.submit(_prompt(n, i), 10) for i, n in enumerate(lengths)]
    probe.run()
    assert [r.state for r in reqs] == ["finished"] * 5
    assert eng.kv.blocks_in_use == 0 and eng.kv.ring_blocks_in_use == 0
    for r in reqs:
        lg = np.asarray(ref.logits(
            params, jnp.asarray([r.prompt + r.out]), **_kw(model.config)))[0]
        first = len(r.prompt) - 1
        want = lg[first:first + len(r.out)]
        got = np.stack(probe.logits[r.rid])[:len(r.out)]
        assert _differ(got, want, dtype) < TOL[dtype], r.rid
        chosen = want[np.arange(len(r.out)), r.out]
        assert (want.max(-1) - chosen).mean() <= (0 if dtype == "float32"
                                                  else TOL[dtype])


def _extended(kv, table, start, stop):
    """Request "r"'s table before positions [start, stop) are written:
    `extend` answers None where it took nothing."""
    taken = kv.extend("r", start, stop)
    return table if taken is None else taken


def _drive(model, params, sched, kv, prompt, n_decode):
    """One request by hand through a builder's programs over `kv`:
    prefill chunk by chunk, then decode steps -> every logits row."""
    progs = ServeProgramBuilder(model, sched).build()
    step = jax.jit(ServeProgramBuilder(model, sched).step_logits)
    C = sched.prefill_chunk
    n_blocks = -(-(len(prompt) + n_decode) // sched.block_size)
    table = kv.alloc("r", n_blocks)
    rows, caches = [], kv.caches
    zero = (np.float32(0), np.int32(0), np.uint32(0))
    for pos in range(0, len(prompt), C):
        chunk = prompt[pos:pos + C]
        toks = np.zeros((1, C), np.int32)
        toks[0, :len(chunk)] = chunk
        table = _extended(kv, table, pos, pos + len(chunk))
        tok, lg, caches = progs["prefill"](
            params, caches, jnp.asarray(toks), np.int32(pos),
            np.int32(len(chunk)), jnp.asarray(table), *zero)
    rows.append(np.asarray(lg))
    tok = int(tok[0])       # behind routed FFNs [sample, rows multiplied]
    for p in range(len(prompt), len(prompt) + n_decode):
        table = _extended(kv, table, p, p + 1)
        lg, caches, _ = step(params, caches, jnp.asarray([tok], jnp.int32),
                             jnp.asarray([p], jnp.int32),
                             jnp.asarray([True]), jnp.asarray(table[None]))
        rows.append(np.asarray(lg[0]))
        tok = int(np.argmax(lg[0]))
    return np.stack(rows)


def test_one_group_with_a_mask_equals_two_groups_with_a_ring():
    """The same request through the same programs: every layer in the
    one group, the window a mask on the whole table, against the sliding
    layers in a ring of their own.  The softmax sees the same keys in
    another order of rows, so equal to float32 rounding through eight
    layers (a few parts in a million of the largest logit)."""
    model, params = _model()
    W = 256 // BS
    base = dict(max_batch=1, prefill_chunk=CHUNK, block_size=BS,
                num_blocks=40, table_width=W)
    prompt = _prompt(3 * WINDOW + 5, 11)
    # one group: what the same layers keep where none has a window
    one = _drive(model, params, ServeSchedule(**base),
                 PagedKVCache(toy_plan(LAYERS, KV, DH, BS, 256), 40,
                              prefix_cache=False), prompt, 2 * CHUNK)
    plan = cache_plan(model.layer_spec(), model.config, _serve(max_batch=1))
    assert plan.ring_blocks == RING // BS and plan.table_width == W
    two = _drive(model, params,
                 ServeSchedule(ring_blocks=RING // BS, **base),
                 PagedKVCache(plan, 40, prefix_cache=False), prompt,
                 2 * CHUNK)
    assert one.std() > 1.0
    np.testing.assert_allclose(two, one, atol=4e-6 * np.abs(one).max())
    want = np.asarray(ref.logits(
        params, jnp.asarray([prompt]), **_kw(model.config)))[0, -1]
    np.testing.assert_allclose(one[0], want, atol=TOL["float32"])


def test_a_ring_without_the_chunks_margin_is_refused():
    model, _ = _model()
    sched = ServeSchedule(max_batch=1, prefill_chunk=CHUNK, block_size=BS,
                          num_blocks=40, table_width=32,
                          ring_blocks=WINDOW // BS)
    with pytest.raises(ValueError, match="one prefill chunk"):
        ServeProgramBuilder(model, sched)


def test_a_request_decodes_the_same_alone_and_in_a_batch():
    model, params = _model()
    alone = ServeEngine(model, params, _serve()).generate(
        [_prompt(2 * WINDOW, 3)], 12)[0]
    eng = ServeEngine(model, params, _serve())
    outs = eng.generate([_prompt(7, 1), _prompt(2 * WINDOW, 3),
                         _prompt(RING + 3, 2)], 12)
    assert outs[1] == alone


def test_short_contexts_are_one_group():
    """Where the ring would be no shorter than the table the cache is
    one group, the window a mask — and the tokens are the same."""
    model, params = _model()
    small = ServeEngine(model, params,
                        _serve(max_seq_len=RING, num_blocks=40))
    assert small.kv.ring_blocks == 0 and len(
        {c[0].shape for c in small.kv.caches}) == 1
    big = ServeEngine(model, params, _serve())
    assert big.kv.ring_blocks == RING // BS
    prompts = [_prompt(WINDOW + 3, 4), _prompt(9, 5)]
    assert small.generate(prompts, 5) == big.generate(prompts, 5)


def test_counters_of_a_decode_step():
    model, params = _model(first_expert=0, experts_held=4)
    eng = ServeEngine(model, params, _serve())
    before = COUNTERS.snapshot()
    lengths = (8, 2 * WINDOW + 1)
    eng.generate([_prompt(n, i) for i, n in enumerate(lengths)], 6)
    d = COUNTERS.delta_since(before)
    # 2 requests x 5 decode steps; a query at position p attends
    # min(p + 1, window) rows in each of 6 sliding layers, p + 1 in 2 full
    held = [n + i + 1 for n in lengths for i in range(5)]
    in_window = sum(min(h, WINDOW) for h in held)
    assert d["serve.window.rows_read"] == {"calls": 10, "bytes": in_window}
    assert d["serve.attn.rows_read"] == {
        "calls": 10, "bytes": 6 * in_window + 2 * sum(held)}
    # the long request ends with 2 x 32 + 6 rows: 9 blocks where the
    # ring holds 6
    assert d["kv.ring_wraps"] == {"calls": 1, "bytes": 3}
    steps = d["serve.decode_steps"]["calls"]
    touched = d["serve.moe.experts_touched"]
    assert touched["calls"] == steps * LAYERS
    # among the 4 held of 8: a live token's 4 choices hold 0..4 of them,
    # and how many of a call's assignments were held only the program
    # knows: they are not counted
    assert 0 < touched["bytes"] <= 10 * TOPK * LAYERS
    # off a TPU 3 slots x top 4 of 8 leave one for each of the 4 held:
    # the masked way, which reads all 4 in every layer
    assert d["serve.moe.experts_streamed"] == {
        "calls": steps * LAYERS, "bytes": steps * LAYERS * 4}
    assert "serve.moe.assignments" not in d
    assert "serve.paged.rows_walked" not in d


def test_counters_of_a_prefill_chunk():
    """`serve.moe.prefill_rows_multiplied`: every chunk's routed layers
    report the assignment rows their product multiplied — counted in
    the program, returned behind the chunk's sample and read with the
    request's first token.  Off a TPU a chunk of 16 tokens runs each of
    the 4 experts held on every row under a mask: 16 x 4 rows a layer,
    the padded tail's too."""
    model, params = _model(first_expert=0, experts_held=4)
    eng = ServeEngine(model, params, _serve())
    chunk = eng.config.prefill_chunk
    assert dropless.routed_way(
        chunk, TOPK, params["blocks"][0]["mlp"]["experts"],
        EXPERTS) == "masked"
    before = COUNTERS.snapshot()
    lengths = (8, 2 * WINDOW + 1)
    eng.generate([_prompt(n, i) for i, n in enumerate(lengths)], 3)
    d = COUNTERS.delta_since(before)
    chunks = d["serve.prefill_chunks"]["calls"]
    assert chunks == sum(-(-n // chunk) for n in lengths) > 2
    assert d["serve.moe.prefill_rows_multiplied"] == {
        "calls": chunks * LAYERS, "bytes": chunks * LAYERS * chunk * 4}
    assert not any(r.chunk_counts for r in eng.scheduler.requests)


def test_decode_appends_the_held_experts_touched_to_its_tokens():
    model, params = _model(first_expert=0, experts_held=EXPERTS // 2)
    eng = ServeEngine(model, params, _serve())
    R, W = 3, eng.kv.table_width + eng.kv.ring_blocks
    out, _, (toks, moved) = eng.programs["decode"](
        eng.params, eng.kv.caches, jnp.zeros((R,), jnp.int32),
        jnp.zeros((R,), jnp.int32), jnp.asarray([True, False, False]),
        jnp.zeros((R, W), jnp.int32), jnp.zeros((R,), jnp.float32),
        jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.uint32))
    # one live token: each of its held assignments touches one expert
    assert out.shape == (R + 1,) and 0 < int(out[R]) <= TOPK * LAYERS
    assert np.array_equal(toks, out[:R]) and moved.tolist() == [1, 0, 0]


# -- two groups of layers under the allocator ------------------------------------


def _ring_plan(layers, windows):
    """`layers` layers of the pattern `windows` under blocks of BS, a
    table of 48 positions, chunks of 8 and two slots."""
    return toy_plan(layers, KV, DH, BS, 48, slots=2, prefill_chunk=8,
                    attention="grouped", kv_heads=KV, layer_windows=windows)


def test_two_groups_under_the_allocator():
    # a ring of window 16 + a chunk of 8 = 24 rows, under a table of 48
    kv = PagedKVCache(_ring_plan(4, (16, 16, 16, 0)), 9, dtype=jnp.bfloat16,
                      prefix_cache=False)
    assert kv.ring_tokens == 24 and kv.table_width == 6
    assert [c[0].shape for c in kv.caches] == [(56, 128)] * 3 + [(72, 128)]
    a = kv.alloc("a", 5)
    assert a.shape == (9,) and (a[5:] == TRASH_BLOCK).all()
    assert TRASH_BLOCK not in a[:5] and kv.ring_blocks_in_use == 0
    a = kv.extend("a", 0, 10)              # positions 0..9: two ring blocks
    assert (a[6:8] != TRASH_BLOCK).all() and a[8] == TRASH_BLOCK
    assert kv.extend("a", 3, 12) is None                # nothing new
    a = kv.extend("a", 16, 17)
    assert sorted(kv.ring_blocks_of("a")) == sorted(a[6:].tolist())
    held = set(kv.ring_blocks_of("a"))
    for p in range(17, 40):                # round and round: never more
        assert set(kv.ring_blocks_of("a")) == held
        assert kv.extend("a", p, p + 1) is None
    assert kv.alloc("b", 4) is None        # group full: 8 blocks, 5 held
    b = kv.extend("b", 0, 24) if kv.alloc("b", 3) is not None else None
    assert kv.ring_blocks_in_use == 6 and not held & set(b[6:].tolist())
    assert TRASH_BLOCK not in b[6:]
    kv.free("a")
    assert kv.ring_blocks_in_use == 3 and kv.blocks_in_use == 3
    c = kv.alloc("c", 5)
    c = kv.extend("c", 40, 48)             # a chunk at ring rows 16..23
    assert set(kv.ring_blocks_of("c")) <= held and c[8] != TRASH_BLOCK
    kv.free("b"), kv.free("c")
    assert kv.ring_blocks_in_use == kv.blocks_in_use == 0


def test_two_groups_refuse_what_they_cannot_hold():
    plan = _ring_plan(2, (16, 0))
    PagedKVCache(plan, 9, dtype=jnp.bfloat16, prefix_cache=False)
    for kw in ({"dtype": "int8"}, {"prefix_cache": True}):
        with pytest.raises(ValueError, match="two groups"):
            PagedKVCache(plan, 9, **dict(
                dict(dtype=jnp.bfloat16, prefix_cache=False), **kw))
    # a pattern whose windows fall on none of the layers: a ring for none
    with pytest.raises(ValueError, match="for some layers"):
        _ring_plan(2, (0, 0, 16))
    # (a ring is whole blocks, for `max_batch` >= 1 slots, by construction:
    # `cache_plan` rounds it up and ServeConfig refuses max_batch < 1)
    assert _ring_plan(2, (13, 0)).ring_blocks == 3


def test_engine_never_holds_more_than_the_ring_and_frees_both_groups():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    most = []
    reqs = [eng.submit(_prompt(n, i), 8)
            for i, n in enumerate([RING - 2, 4 * WINDOW, 6])]
    while eng.has_work():
        eng.step()
        most.append(max([len(eng.kv.ring_blocks_of(r.rid)) for r in reqs]))
    assert max(most) == RING // BS
    assert eng.kv.ring_blocks_in_use == eng.kv.blocks_in_use == 0
    first = [r.out for r in reqs]
    # a second wave through the freed blocks of both groups: the same
    # tokens, and nothing but inactive slots' rows in the trash blocks
    again = eng.generate([r.prompt for r in reqs], 8)
    assert again == first


# -- refused, by name ----------------------------------------------------------


@pytest.mark.parametrize("serve,match", [
    (dict(prefix_cache=True), "prefix_cache=True over grouped rows"),
    (dict(draft_len=2), "draft_len > 0 over grouped rows"),
    (dict(kv_dtype="int8"), "kv_dtype 'int8' over grouped rows"),
    (dict(kv_dtype="int4"), "kv_dtype 'int4' over grouped rows"),
    (dict(quantized_weights="int8"), "quantized_weights over grouped rows"),
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
                       match="sessions over grouped rows"):
        eng.submit(_prompt(5), 4, session_id="s")
    with pytest.raises(NotImplementedError,
                       match="a mesh of 2 devices over grouped rows"):
        ServeEngine(model, params, _serve(),
                    mesh_info=make_mesh(model=2, data=1,
                                        devices=jax.devices()[:2]))


def test_the_registry_is_asked_for_each_kind_of_layer(native):
    """What `_grouped_attend` hands the registry says what the layer's
    rows are: on the chip a decode call takes the walk — a full layer's,
    and a sliding layer's in its ring or on the table — and a prefill
    chunk of these narrow heads keeps the gather, whatever its rows; at
    heads of whole lane tiles the chunk walks too, under a window where
    its ring holds `window + chunk - 1` rows (the engine's does)."""
    model, params = _model()
    spec, cfg = model.layer_spec(), model.config
    sched = ServeSchedule(max_batch=3, prefill_chunk=CHUNK, block_size=BS,
                          num_blocks=120, table_width=256 // BS,
                          ring_blocks=RING // BS)
    info = serving_layers.grouped_info(spec, cfg, sched, 1, jnp.float32)
    assert info["kv_heads"] == KV and info["num_heads"] == HEADS
    assert (info["window"], info["ring"]) == (0, False)
    assert info["table_width"] == 256 // BS
    # over a ring the table the call hands over is the ring's entries
    assert serving_layers.grouped_info(
        spec, cfg, sched, 1, jnp.float32, WINDOW, True)["table_width"] == \
        RING // BS
    ask = lambda *a, spec=spec, cfg=cfg, sched=sched, **kw: \
        registry.resolve_impl(
            "grouped_attention", info=serving_layers.grouped_info(
                spec, cfg, sched, *a), **kw)
    assert ask(1, jnp.float32) == "pallas"
    assert ask(1, jnp.float32, WINDOW, True) == "pallas"
    assert ask(1, jnp.float32, WINDOW, False) == "pallas"
    assert ask(CHUNK, jnp.float32) == "jnp"
    assert ask(CHUNK, jnp.float32, WINDOW, True) == "jnp"
    assert ask(1, jnp.bfloat16) == "jnp"     # blocks of 8 rows of bf16
    for kind in ((WINDOW, True), (WINDOW, False)):
        with pytest.raises(RuntimeError, match=(
                f"{KV} K/V heads of {DH} values.*whole 128-lane tiles")):
            ask(CHUNK, jnp.float32, *kind, impl="pallas")
    # heads of whole lane tiles: the chunk walks each kind of layer's run
    wide = _model(head_dim=128)[0]
    wide = dict(spec=wide.layer_spec(), cfg=wide.config)
    assert ask(CHUNK, jnp.float32, **wide) == "pallas"
    assert ask(CHUNK, jnp.float32, WINDOW, True, **wide) == "pallas"
    assert ask(CHUNK, jnp.float32, WINDOW, False, **wide) == "pallas"
    # a ring a block short of `window + chunk - 1` rows keeps the gather
    short = sched._replace(ring_blocks=RING // BS - 1)
    assert ask(CHUNK, jnp.float32, WINDOW, True, sched=short, **wide) == "jnp"
    with pytest.raises(RuntimeError, match=(
            f"a ring of {RING - BS} rows under a window of {WINDOW}.*"
            f"a run of {WINDOW + CHUNK - 1} rows")):
        ask(CHUNK, jnp.float32, WINDOW, True, sched=short, impl="pallas",
            **wide)


@pytest.mark.parametrize("way,head_dim", [("oracle", 128), ("kernel", 128),
                                          ("kernel", DH)])
def test_prefill_rows_walked_is_what_each_kind_of_layer_fetches(
        way, head_dim, chip_rule):
    """`serve.attn.prefill_rows_walked`: a full layer fetches, for a
    chunk, the request's rows up to the chunk's last position rounded up
    to a block where its prefill call is the walk — on the chip, at
    heads of whole lane tiles — and the table's whole width where it is
    the gather; a sliding layer there the blocks from the one that holds
    its FIRST query's lower bound — counted here position by position,
    for chunks before and after the ring's wrap — and its whole ring
    where it gathers.  The kernel serves the oracle's tokens."""
    import contextlib

    model, params = _model(head_dim=head_dim)
    serve = _serve()
    lengths = (8, 2 * WINDOW + 1, CHUNK + 3)
    prompts = [_prompt(n, i) for i, n in enumerate(lengths)]
    with chip_rule("grouped_attention") if way == "kernel" \
            else contextlib.nullcontext():
        eng = ServeEngine(model, params, serve)
        before = COUNTERS.snapshot()
        out = eng.generate(prompts, 2)
    d = COUNTERS.delta_since(before)
    walks = way == "kernel" and head_dim == 128
    # (full layers, sliding layers) x (a decode step, a prefill chunk)
    assert [w[1] for w in eng._counted["grouped"].walks] == [walks, walks]
    # a chunk's last position is its padded tail's
    ends = [start + CHUNK for n in lengths for start in range(0, n, CHUNK)]
    table = eng.kv.table_width * BS
    if walks:
        full = sum(min(-(-e // BS) * BS, table) for e in ends)
        # the blocks that hold positions max(0, e - chunk - window + 1)
        # .. e - 1, what the chunk's first query and its last see
        sliding = sum(BS * len({p // BS for p in range(
            max(0, e - CHUNK - WINDOW + 1), e)}) for e in ends)
        assert sliding < len(ends) * RING and max(ends) > RING   # the wrap
    else:
        full, sliding = len(ends) * table, len(ends) * RING
    assert d["serve.attn.prefill_rows_walked"] == {
        "calls": len(ends), "bytes": 2 * full + 6 * sliding}
    assert d["serve.prefill_chunks"]["calls"] == len(ends)
    if walks:
        assert out == ServeEngine(model, params, serve).generate(prompts, 2)


@pytest.mark.parametrize("way,ringed", [("oracle", True), ("kernel", True),
                                        ("kernel", False)])
def test_rows_walked_is_what_each_kind_of_layer_fetches(way, ringed,
                                                        chip_rule):
    """`serve.attn.rows_walked`: a full layer fetches a slot's cached
    length rounded up to a block where its decode is the walk and the
    table's whole width where it is the gather; a sliding layer the
    blocks its window lies in — counted here position by position, for
    slots before and after the ring's wrap — where its decode is the
    walk, and its whole run — its ring, or (one group: the ring would be
    no shorter than the table) the table under the window — where it is
    the gather.  The kernel serves the oracle's tokens."""
    import contextlib

    model, params = _model()
    serve = _serve() if ringed else _serve(max_seq_len=RING)
    lengths = (8, 2 * WINDOW + 1) if ringed else (8, 21)
    prompts = [_prompt(n, i) for i, n in enumerate(lengths)]
    with chip_rule("grouped_attention") if way == "kernel" \
            else contextlib.nullcontext():
        eng = ServeEngine(model, params, serve)
        before = COUNTERS.snapshot()
        out = eng.generate(prompts, 6)
    d = COUNTERS.delta_since(before)
    assert bool(eng.kv.ring_blocks) == ringed
    assert [w[0] for w in eng._counted["grouped"].walks] == \
        [way == "kernel"] * 2
    held = [n + i + 1 for n in lengths for i in range(5)]
    table = eng.kv.table_width * BS
    run = RING if ringed else table
    if way == "kernel":
        full = sum(-(-h // BS) * BS for h in held)
        # the blocks that hold positions max(0, h - window) .. h - 1
        sliding = sum(BS * len({p // BS for p in range(
            max(0, h - WINDOW), h)}) for h in held)
        assert sliding < 10 * run
        assert not ringed or max(held) > RING > min(held)   # the wrap
    else:
        full, sliding = 10 * table, 10 * run
    assert d["serve.attn.rows_walked"] == {
        "calls": 10, "bytes": 2 * full + 6 * sliding}
    assert d["serve.attn.rows_read"]["bytes"] <= \
        d["serve.attn.rows_walked"]["bytes"]
    if way == "kernel":
        # decode's eight layers, and no other call, took the kernel
        assert d["kernel.dispatches"]["calls"] == LAYERS
        assert out == ServeEngine(model, params, serve).generate(prompts, 6)
    else:
        assert "kernel.dispatches" not in d


# -- the layer spec -------------------------------------------------------------


def test_layer_spec_of_the_new_kinds():
    model, _ = _model(first_expert=2, experts_held=4)
    spec = model.layer_spec()
    assert (spec.norm, spec.positions, spec.attention, spec.ffn, spec.head,
            spec.residual) == ("layernorm_gain", "per_layer", "grouped",
                               "routed_experts", "tied", "parallel")
    assert spec.layer_windows == (WINDOW, WINDOW, WINDOW, 0)
    assert spec.layer_positions == ("rope", "rope", "rope", "none")
    assert [spec.window_of(i) for i in (0, 3, 6, 7)] == [WINDOW, 0, WINDOW, 0]
    assert spec.rotates(4) and not spec.rotates(7)
    assert (spec.kv_heads, spec.top_k, spec.scoring, spec.renormalize,
            spec.shared, spec.held) == (KV, TOPK, "sigmoid", True,
                                        "average", (2, 4))
    assert serving_layers.check_spec(spec) == spec


@pytest.mark.parametrize("change,match", [
    (dict(kv_heads=0), "kv_heads"),
    (dict(attention="paged"), "kv_heads"),
    (dict(layer_windows=(4, -1)), "window is 0"),
    (dict(layer_positions=()), "per_layer positions"),
    (dict(layer_positions=("rope", "alibi")), "per_layer positions"),
    (dict(positions="rope"), "per_layer positions"),
    (dict(residual="post"), "is not one of"),
    (dict(scoring="tanh"), "is not one of"),
    (dict(shared="max"), "is not one of"),
    (dict(norm="layernorm_nobias"), "is not one of"),
    (dict(first_expert=2, experts_held=0), "a share of the experts"),
    (dict(ffn="silu_gated", top_k=0), "describe a routed_experts FFN"),
])
def test_layer_spec_validate_refuses(change, match):
    good = Cohere2Moe(_config(experts_held=4)).layer_spec()
    with pytest.raises(ValueError, match=match):
        good._replace(**change).validate()


@pytest.mark.parametrize("change", [
    dict(scoring="softmax"), dict(renormalize=False), dict(shared="sum"),
    dict(scoring="softmax", renormalize=False, shared="sum"),
    dict(experts_held=2, first_expert=4), dict(norm="rmsnorm"),
])
def test_the_parallel_block_computes_what_the_spec_says(change):
    """Scoring, renormalisation, the shared experts' combine and the
    share held are the spec's to say: the routed FFN against the sum
    written out."""
    model, params = _model()
    spec = serving_layers.check_spec(
        model.layer_spec()._replace(**change))
    p = params["blocks"][2]["mlp"]
    first, count = spec.held or (0, EXPERTS)
    e = {k: v[first:first + count] for k, v in p["experts"].items()}
    h = jax.random.normal(jax.random.PRNGKey(7), (10, 64))
    got = c2.expert_ffn(spec, model.config, dict(p, experts=e), h)[0]
    logits = h @ p["router"]
    s = jax.nn.softmax(logits, -1) if spec.scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    w, idx = jax.lax.top_k(s, TOPK)
    if spec.renormalize:
        w = w / w.sum(-1, keepdims=True)
    gated = lambda g, u, d: (jax.nn.silu(h @ g) * (h @ u)) @ d
    each = jnp.stack([gated(e["gate"][i], e["up"][i], e["down"][i])
                      for i in range(count)])                # [count, T, D]
    weight = jnp.zeros((10, EXPERTS)).at[
        jnp.arange(10)[:, None], idx].set(w)[:, first:first + count]
    shared = gated(p["shared"]["gate"], p["shared"]["up"],
                   p["shared"]["down"])
    want = jnp.einsum("etd,te->td", each, weight) + \
        (shared / SHARED if spec.shared == "average" else shared)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if change != dict(norm="rmsnorm"):   # and the field decides something
        base = c2.expert_ffn(model.layer_spec(), model.config, p, h)[0]
        assert np.abs(np.asarray(base - got)).max() > 1e-3


@pytest.mark.parametrize("change,match", [
    (dict(residual="sequential"), "parallel block over grouped"),
    (dict(ffn="silu_gated", top_k=0, scoring="softmax", renormalize=False,
          shared="sum"), "parallel block over grouped"),
])
def test_serving_refuses_blocks_it_has_not_built(change, match):
    good = Cohere2Moe(_config()).layer_spec()
    with pytest.raises(NotImplementedError, match=match):
        serving_layers.check_spec(good._replace(**change))
    gpt = LayerSpec(norm="layernorm", positions="learned", attention="paged",
                    ffn="gelu_mlp", head="tied", eps=1e-5)
    assert serving_layers.check_spec(gpt) == gpt
    with pytest.raises(NotImplementedError, match=match):
        serving_layers.check_spec(gpt._replace(residual="parallel"))


@pytest.mark.parametrize("change", [dict(shared="average")])
def test_the_sequential_block_sums_its_shared_experts(change):
    """What else a routed FFN's spec says the sequential block reads as
    the parallel one does (tests/test_deepseek_v2.py)."""
    routed = LayerSpec(norm="rmsnorm", positions="rope", attention="latent",
                       ffn="routed_experts", head="untied", eps=1e-6,
                       latent_width=24, top_k=2).validate()
    assert serving_layers.check_spec(routed) == routed
    with pytest.raises(NotImplementedError, match="sequential block"):
        serving_layers.check_spec(routed._replace(**change).validate())
