"""GLM-5.2 through the serving engine: latent attention over a learned
selection of the cached rows that "full" layers compute and "shared"
layers take over, index keys beside the latent rows, and sigmoid routing
under a selection bias over a chip's share of the experts — against the
plain reference (`benchmarks/reference/glm_moe_dsa.py`) on seeded
weights at toy widths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import glm_moe_dsa as ref
from deepspeed_tpu.models import LayerSpec, cohere2_moe
from deepspeed_tpu.models.glm_moe_dsa import (GlmMoeDsa, GlmMoeDsaConfig,
                                              select_mask)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import PagedKVCache, ServeConfig, ServeEngine
from deepspeed_tpu.serving import layers as serving_layers
from deepspeed_tpu.serving import sparse
from toy_plans import toy_plan

VOCAB, TOPK, EXPERTS, HELD, INDEX_TOPK = 97, 4, 16, 4, 8
KINDS = ("full", "shared", "shared", "shared", "full")


def _config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=96, num_layers=5, num_heads=4,
                d_model=32, q_lora_rank=16, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=12,
                index_heads=3, index_head_dim=8, index_topk=INDEX_TOPK,
                indexer_types=KINDS, d_ff=48, first_k_dense=1,
                num_experts=EXPERTS, top_k=TOPK, d_expert=24,
                experts_held=HELD, first_expert=4, init_std=0.3,
                router_std=0.5, bias_std=0.3, query_std=0.6)
    base.update(kw)
    return GlmMoeDsaConfig(**base)


def _kw(cfg):
    return dict(heads=cfg.num_heads, nope=cfg.qk_nope_head_dim,
                rope=cfg.qk_rope_head_dim, rank=cfg.kv_lora_rank,
                index_heads=cfg.index_heads, topk=cfg.index_topk,
                indexer=cfg.indexer_types, dense_layers=cfg.first_k_dense,
                top_k=cfg.top_k, first_expert=cfg.first_expert,
                route_scale=cfg.route_scale, eps=cfg.rms_norm_eps,
                index_eps=cfg.index_norm_eps, theta=cfg.rope_theta)


def _serve(**kw):
    base = dict(block_size=4, num_blocks=80, max_batch=3, prefill_chunk=8,
                max_seq_len=96, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


def _model(dtype=jnp.float32, **kw):
    model = GlmMoeDsa(_config(param_dtype=dtype, **kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


@pytest.fixture
def small_tiles(monkeypatch):
    """Several tiles a prefill chunk's walk, at toy lengths."""
    monkeypatch.setattr(sparse, "KEY_TILE", 16)


# -- the uncached forward against the reference -------------------------------


@pytest.mark.parametrize("absorbed", [False, True],
                         ids=["expanded", "absorbed"])
def test_forward_matches_the_plain_reference(absorbed):
    """40 positions, five times `index_topk`: from position 8 on a query
    attends 8 chosen rows, in the "full" layers and in the "shared" ones
    behind them."""
    model, params = _model()
    toks = jnp.asarray([_prompt(40, 1), _prompt(40, 2)])
    got, chosen = jax.jit(lambda p, t: model.apply(
        p, t, absorbed=absorbed, return_selected=True))(params, toks)
    want, sets = ref.logits(params, toks, return_selected=True,
                            **_kw(model.config))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-4
    assert len(chosen) == len(sets) == 2          # layers 0 and 4
    for mine, theirs in zip(chosen, sets):
        assert np.array_equal(np.asarray(mine), np.asarray(theirs))
        counts = np.asarray(mine).sum(-1)
        assert (counts == np.minimum(np.arange(40) + 1, INDEX_TOPK)).all()


def test_the_selection_changes_the_result():
    """Attending every row gives other logits: the selection bites."""
    model, params = _model()
    every, _ = _model(index_topk=96)
    toks = jnp.asarray([_prompt(40, 1)])
    a = jax.jit(model.apply)(params, toks)
    b = jax.jit(every.apply)(params, toks)
    assert np.abs(np.asarray(a - b))[0, :INDEX_TOPK].max() < 1e-5
    assert np.abs(np.asarray(a - b))[0, INDEX_TOPK:].max() > 1e-2


@pytest.mark.parametrize("case", ["ties", "fewer_than_topk", "none_visible"])
def test_select_mask_by_hand(case):
    scores = jnp.asarray([[3., 1., 3., 3., 0., 2., 3., -1.]])
    seen = jnp.ones((1, 8), bool)
    want = [True, False, True, True, False, False, False, False]
    if case == "fewer_than_topk":
        seen = jnp.arange(8)[None] < 2
        want = [True, True] + [False] * 6
    if case == "none_visible":
        seen = jnp.zeros((1, 8), bool)
        want = [False] * 8
    got = select_mask(scores, seen, 3)
    assert np.asarray(got)[0].tolist() == want        # ties: lower first


# -- prefill in chunks, then decode, through the engine ---------------------------


def test_prefill_then_decode_matches_the_reference_forward(small_tiles):
    """Four requests through three slots (a slot is reused; a prompt of
    37 spans five chunks and three key tiles, one of 5 stays under
    `index_topk` rows until it has decoded three tokens): at every
    generated position the logits the engine drew from are the
    reference's full forward's, and the token its argmax."""
    from test_evabyte import Probe

    model, params = _model()
    probe = Probe(model, params, _serve())
    eng = probe.engine
    reqs = [eng.submit(_prompt(n, i), 10)
            for i, n in enumerate([5, 37, 22, 9])]
    probe.run()
    assert [r.state for r in reqs] == ["finished"] * 4
    assert eng.kv.blocks_in_use == 0
    for r in reqs:
        lg = np.asarray(ref.logits(params, jnp.asarray([r.prompt + r.out]),
                                   **_kw(model.config)))[0]
        first = len(r.prompt) - 1
        want = lg[first:first + len(r.out)]
        got = np.stack(probe.logits[r.rid])[:len(r.out)]
        assert np.abs(got - want).max() < 2e-4, r.rid
        assert (want.argmax(-1) == np.asarray(r.out)).all()


def test_the_programs_chosen_sets_are_the_references(small_tiles,
                                                     monkeypatch):
    """What `dsa_select` hands to attention — a mask over positions in a
    prefill chunk, a list of pool rows in a decode step — is, query by
    query, the set the reference's plain top-k chooses."""
    seen_masks, seen_lists = [], []

    def spy_mask(orig):
        def select(scores, seen, topk, **kw):
            mask = orig(scores, seen, topk, **kw)
            jax.debug.callback(
                lambda m, s: seen_masks.append((np.asarray(m),
                                                np.asarray(s))), mask, seen)
            return mask
        return select

    def spy_step(orig):
        def select(scores, q_pos, tables, topk, bs):
            rows, chosen = orig(scores, q_pos, tables, topk, bs)
            jax.debug.callback(
                lambda *a: seen_lists.append(tuple(map(np.asarray, a))),
                rows, chosen, q_pos, tables)
            return rows, chosen
        return select

    monkeypatch.setattr(sparse, "select_mask", spy_mask(sparse.select_mask))
    monkeypatch.setattr(sparse, "select_step", spy_step(sparse.select_step))
    model, params = _model()
    eng = ServeEngine(model, params, _serve(max_batch=1))
    prompt = _prompt(29, 5)
    out = eng.generate([prompt], 6)[0]
    jax.effects_barrier()
    _, sets = ref.logits(params, jnp.asarray([prompt + out]),
                         return_selected=True, **_kw(model.config))
    sets = [np.asarray(s)[0] for s in sets]
    # prefill: 4 chunks x 2 full layers, in layer order within a chunk
    assert len(seen_masks) == 8
    for n, (mask, seen) in enumerate(seen_masks):
        q_pos = seen[0].sum(-1) - 1
        for t, p in enumerate(q_pos):
            if p < len(prompt):                 # a padded tail is not read
                assert np.array_equal(mask[0, t, :p + 1],
                                      sets[n % 2][p, :p + 1]), (n, p)
    # decode: 5 steps x 2 full layers; pool rows back to positions
    assert len(seen_lists) == 10
    bs = eng.config.block_size
    for n, (rows, chosen, q_pos, tables) in enumerate(seen_lists):
        p = int(q_pos[0])
        where = {int(b): i for i, b in enumerate(tables[0]) if b}
        at = {where[int(r) // bs] * bs + int(r) % bs
              for r, c in zip(rows[0], chosen[0]) if c}
        assert at == set(np.flatnonzero(sets[n % 2][p]).tolist()), (n, p)
        assert len(at) == min(p + 1, INDEX_TOPK)


def test_a_request_decodes_the_same_alone_and_in_a_batch(small_tiles):
    model, params = _model()
    alone = ServeEngine(model, params, _serve()).generate([_prompt(19, 3)],
                                                          12)[0]
    eng = ServeEngine(model, params, _serve())
    outs = eng.generate([_prompt(7, 1), _prompt(19, 3), _prompt(33, 2)], 12)
    assert outs[1] == alone
    again = eng.generate([_prompt(19, 3)], 12)      # through freed blocks
    assert again[0] == alone and eng.kv.blocks_in_use == 0


# -- routing: the bias chooses, the share adds up -----------------------------------


def test_the_bias_chooses_and_does_not_weigh():
    key = jax.random.PRNGKey(3)
    h = jax.random.normal(key, (64, 32))
    router = jax.random.normal(jax.random.fold_in(key, 1), (32, EXPERTS))
    bias = jax.random.normal(jax.random.fold_in(key, 2), (EXPERTS,)) * 0.3
    w, idx = dropless.route(h, router, TOPK, scoring="sigmoid",
                            renormalize=True, select_bias=bias, scale=2.5)
    plain_w, plain_idx = dropless.route(h, router, TOPK, scoring="sigmoid",
                                        renormalize=True)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        h, router, precision=jax.lax.Precision.HIGHEST)))
    want_idx = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :TOPK]
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(want_idx))
    chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(-1, keepdims=True) * 2.5,
        rtol=1e-6)
    # the bias overrules the scores for a visible share of the tokens
    differs = (np.sort(np.asarray(idx)) != np.sort(np.asarray(plain_idx))
               ).any(-1).mean()
    assert differs > 0.5
    np.testing.assert_allclose(np.asarray(plain_w).sum(-1), 1.0, rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their routed parts, with the
    shared expert counted once, are the uncut layer's reference."""
    whole_model, whole = _model(experts_held=0, first_expert=0)
    mlp = whole["blocks"][2]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(7), (24, 32))
    kw = _kw(whole_model.config)
    with jax.default_matmul_precision("highest"):
        want = ref._routed(h, mlp, top_k=TOPK, first_expert=0,
                           route_scale=kw["route_scale"])
        shared = ref._gated(h, mlp["shared"]["gate"], mlp["shared"]["up"],
                            mlp["shared"]["down"])
    total = shared
    for first in range(0, EXPERTS, HELD):
        model = GlmMoeDsa(_config(first_expert=first))
        part = dict(mlp, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + HELD], mlp["experts"]))
        y, touched = cohere2_moe.expert_ffn(
            model.layer_spec(), model.config, part, h,
            live=jnp.ones((24,), bool))
        assert 0 < int(touched) <= HELD
        mine = ref._routed(h, part, top_k=TOPK, first_expert=first,
                           route_scale=kw["route_scale"])
        assert np.abs(np.asarray(y - mine)).max() < 1e-4
        total = total + (y - shared)
    assert np.abs(np.asarray(total - want)).max() < 1e-4


# -- the second row under the one allocator ---------------------------------------


def test_index_keys_lie_in_the_full_layers_only():
    full = ("full", "shared", "shared", "shared")
    kv = PagedKVCache(toy_plan(5, 4, 32, 8, 32, attention="latent",
                               latent_width=576, layer_indexers=full,
                               index_width=128, index_topk=16), 9,
                      dtype=jnp.bfloat16, prefix_cache=False)
    assert [g.layers for g in kv.plan.groups] == [(0, 4), (1, 2, 3)]
    assert [len(e) for e in kv.caches] == [2, 1, 1, 1, 2]
    assert kv.caches[0][0].shape == (72, 640)
    assert kv.caches[0][1].shape == kv.caches[4][1].shape == (72, 128)
    assert kv.index_nbytes() == 2 * 72 * 128 * 2
    assert kv.nbytes() == 5 * 72 * 640 * 2 + kv.index_nbytes()
    assert kv.bytes_per_block() == kv.nbytes() // 9
    assert "index keys of 128 in layers [0, 4]" in kv.describe()
    a = kv.alloc("a", 3)
    assert kv.blocks_in_use == 3 and (a[3:] == 0).all()
    kv.free("a")
    plain = PagedKVCache(toy_plan(5, 4, 32, 8, 32, attention="latent",
                                  latent_width=576), 9, dtype=jnp.bfloat16,
                         prefix_cache=False)
    assert plain.index_nbytes() == 0 and "index keys" not in plain.describe()
    with pytest.raises(ValueError, match="beside latent rows"):
        toy_plan(2, 4, 32, 8, 32, layer_indexers=("full",), index_width=16)


def test_the_engine_lays_out_what_the_spec_says():
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    keyed, shared = eng.plan.groups
    assert keyed.layers == (0, 4) and keyed.arrays[1] == ("key", 1, 8)
    assert shared.layers == (1, 2, 3) and len(shared.arrays) == 1
    assert [len(e) for e in eng.kv.caches] == [2, 1, 1, 1, 2]
    assert "indexer" in params["blocks"][0]["attn"]
    assert all("indexer" not in params["blocks"][i]["attn"]
               for i in (1, 2, 3))
    assert params["blocks"][1]["mlp"]["experts"]["gate"].shape[0] == HELD
    assert params["blocks"][1]["mlp"]["router"].shape[1] == EXPERTS


def test_counters_of_a_decode_step(small_tiles):
    model, params = _model()
    eng = ServeEngine(model, params, _serve())
    before = COUNTERS.snapshot()
    eng.generate([_prompt(5, 1), _prompt(21, 2)], 6)
    d = COUNTERS.delta_since(before)
    # 2 requests x 5 decode steps (the first token comes from prefill);
    # a query at position p scores p + 1 keys in each of the 2 full
    # layers and attends min(p + 1, 8) rows in each of the 5 layers
    held = [n + i + 1 for n in (5, 21) for i in range(5)]
    assert d["serve.sparse.keys_scored"] == {"calls": 10 * 2,
                                             "bytes": sum(held) * 2}
    assert d["serve.sparse.rows_selected"] == {
        "calls": 10 * 5, "bytes": 5 * sum(min(h, INDEX_TOPK) for h in held)}
    # the decode program gathers its list's 8 rows a slot a layer
    assert d["serve.sparse.rows_fetched"] == {"calls": 10 * 5,
                                              "bytes": 10 * 5 * INDEX_TOPK}
    # 3 shared layers in every decode step and every prefill chunk
    steps = d["serve.decode_steps"]["calls"]
    chunks = d["serve.prefill_chunks"]["calls"]
    assert chunks == 1 + 3 and d["serve.sparse.selections_shared"] == {
        "calls": 3 * (steps + chunks), "bytes": 0}
    assert "serve.mla.rows_read" not in d
    assert d["serve.moe.experts_touched"]["calls"] == steps * 4
    assert "serve.moe.assignments" not in d          # behind a share


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


def test_layer_spec_says_which_layers_choose():
    spec = GlmMoeDsa(_config()).layer_spec()
    assert spec.layer_indexers == KINDS and spec.index_topk == INDEX_TOPK
    assert [spec.indexer_of(i) for i in range(5)] == list(KINDS)
    assert spec.index_layers(5) == (0, 4)
    assert spec.held == (4, HELD) and spec.select_bias
    assert spec.route_scale == 2.5 and spec.scoring == "sigmoid"
    assert serving_layers.check_spec(spec) == spec
    plain = LayerSpec(norm="rmsnorm", positions="rope", attention="latent",
                      ffn="routed_experts", head="untied", eps=1e-6,
                      latent_width=24, top_k=2).validate()
    assert plain.indexer_of(3) is None and plain.index_layers(9) == ()
    # the published list from its two numbers: 0-2 full, then 6, 10, ...
    kinds = GlmMoeDsaConfig().indexer_types
    assert len(kinds) == 78 and [i for i, k in enumerate(kinds)
                                 if k == "full"][:6] == [0, 1, 2, 6, 10, 14]
    assert kinds.count("full") == 21


@pytest.mark.parametrize("change,match", [
    (dict(layer_indexers=("shared", "full")), "a \"full\" one first"),
    (dict(layer_indexers=("full", "half")), "layer_indexers says"),
    (dict(index_topk=0), "index_topk, index_heads and index_width"),
    (dict(layer_indexers=()), "index_topk, index_heads and index_width"),
    (dict(attention="paged", latent_width=0), "latent"),
    (dict(ffn="silu_gated", top_k=0, scoring="softmax", renormalize=False,
          experts_held=0, first_expert=0, route_scale=1.0, dense_layers=0),
     "select_bias and route_scale describe a routed_experts FFN"),
])
def test_layer_spec_validate_refuses(change, match):
    spec = GlmMoeDsa(_config()).layer_spec()
    with pytest.raises(ValueError, match=match):
        spec._replace(**change).validate()


@pytest.mark.parametrize("module", [
    "deepspeed_tpu", "deepspeed_tpu.serving", "deepspeed_tpu.models"])
def test_the_family_is_imported_only_when_it_is_built(module):
    """Nothing of this family at import of the package, of serving or of
    the model zoo: another cell's set-up pays nothing for it."""
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "bad = [m for m in sys.modules if 'glm_moe_dsa' in m "
            "or m.endswith('serving.sparse')]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})
