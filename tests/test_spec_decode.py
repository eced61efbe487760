"""Speculative decoding over the quantized paged KV cache
(deepspeed_tpu/serving + runtime/comm/quant.py row kernels).

THE acceptance pin: the speculative engine is token-identical to the
non-speculative engine at MATCHED kv_dtype for every (kv_dtype x
draft_len x admission) cell — speculation changes WHEN tokens arrive,
never WHICH — and at dense/bf16 KV both are bitwise-identical to
`models/generation.generate`.  Around the pin: the row-quant kernels,
the scheduler's draft-aware block budget, the acceptance counters, and
the serve_bench tier-1 spec lane.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import GPT, gpt2_config
from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.runtime.comm.quant import (dequantize_rows,
                                              quantize_rows)
from deepspeed_tpu.serving import (FINISHED, PagedKVCache, ServeConfig,
                                   ServeEngine, ServeProgramBuilder,
                                   ServeSchedule, kv_block_bytes,
                                   resolve_kv_dtype)
from deepspeed_tpu.serving.scheduler import Request, Scheduler
from toy_plans import toy_plan

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

VOCAB = 64
MAX_SEQ = 64
BS = 4            # KV block size
WIDTH = MAX_SEQ // BS


@pytest.fixture(scope="module")
def model_and_params():
    # head_dim 8 (even) so int4 packing is legal
    model = GPT(gpt2_config("nano", num_layers=2, num_heads=4, d_model=32,
                            vocab_size=VOCAB, max_seq_len=MAX_SEQ))
    return model, model.init(jax.random.PRNGKey(1))


def _cfg(**over):
    base = dict(block_size=BS, num_blocks=40, max_batch=3,
                prefill_chunk=8, max_seq_len=MAX_SEQ)
    base.update(over)
    return ServeConfig(**base)


# ONE compiled program set per (kv wire-or-dense, draft_len) shared by
# every engine in the module — engines differ only in allocator state
# and admission policy, and bf16/fp32 share a "dense" program (jit
# re-specializes per cache dtype on its own).
_PROGRAMS = {}


def _engine(model_and_params, **over):
    model, params = model_and_params
    cfg = _cfg(**over)
    mode, _ = resolve_kv_dtype(model.config.param_dtype
                               if cfg.kv_dtype is None else cfg.kv_dtype)
    key = (mode if mode in ("int8", "int4") else "dense",
           int(cfg.draft_len))
    if key not in _PROGRAMS:
        sched = ServeSchedule(
            max_batch=cfg.max_batch, prefill_chunk=cfg.prefill_chunk,
            block_size=BS, num_blocks=cfg.num_blocks, table_width=WIDTH,
            kv_dtype=key[0], draft_len=key[1])
        _PROGRAMS[key] = ServeProgramBuilder(model, sched).build()
    return ServeEngine(model, params, cfg, programs=_PROGRAMS[key])


def _prompts(seed=0):
    """Repetitive prompts (pattern x 4) — the self-speculative drafter's
    home turf, so draft>0 lanes actually accept — plus one random."""
    rs = np.random.RandomState(seed)
    ps = [(rs.randint(0, VOCAB, (n,)).tolist() * 4)
          for n in (3, 4)]
    ps.append(rs.randint(0, VOCAB, (7,)).tolist())
    return ps


_BASELINES = {}


def _baseline(model_and_params, kv, prompts, n=10, **kw):
    """Non-speculative one-at-a-time oracle outputs at kv_dtype `kv`."""
    key = (kv, tuple(map(tuple, prompts)), n,
           tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(kw.items())))
    if key not in _BASELINES:
        outs = []
        for i, p in enumerate(prompts):
            eng = _engine(model_and_params, kv_dtype=kv, draft_len=0)
            seeds = [kw["seeds"][i]] if "seeds" in kw else None
            extra = {k: v for k, v in kw.items() if k != "seeds"}
            if seeds is not None:
                extra["seeds"] = seeds
            outs.append(eng.generate([p], n, **extra)[0])
        _BASELINES[key] = outs
    return _BASELINES[key]


# -- row-quant kernels (the cache's storage codec) --------------------------


@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_row_quant_roundtrip_error_bounded(wire):
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(9, 4, 8).astype(np.float32) * 5.0)
    payload, scales = quantize_rows(x, wire)
    assert scales.dtype == jnp.float16 and scales.shape == (9, 4)
    if wire == "int8":
        assert payload.dtype == jnp.int8 and payload.shape == (9, 4, 8)
    else:
        assert payload.dtype == jnp.uint8 and payload.shape == (9, 4, 4)
    y = dequantize_rows(payload, scales, wire)
    # error <= half a step of the per-row scale
    err = np.abs(np.asarray(y) - np.asarray(x))
    bound = np.asarray(scales, np.float32)[..., None] * 0.5 + 1e-7
    assert (err <= bound).all()


@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_row_quant_zero_row_roundtrips_exactly(wire):
    x = jnp.zeros((5, 2, 8), jnp.float32)
    payload, scales = quantize_rows(x, wire)
    y = dequantize_rows(payload, scales, wire)
    assert (np.asarray(y) == 0.0).all()  # matches dense zero-init


def test_row_quant_int4_odd_trailing_axis_rejected():
    with pytest.raises(ValueError, match="even"):
        quantize_rows(jnp.zeros((2, 7), jnp.float32), "int4")


# -- quantized cache layout / sizing ----------------------------------------


def test_resolve_kv_dtype_aliases_and_typos():
    assert resolve_kv_dtype("bf16") == ("dense", jnp.bfloat16)
    assert resolve_kv_dtype("float32") == ("dense", jnp.float32)
    assert resolve_kv_dtype("int8") == ("int8", None)
    assert resolve_kv_dtype("int4") == ("int4", None)
    assert resolve_kv_dtype(jnp.float16) == ("dense", jnp.float16)
    with pytest.raises(ValueError, match="kv_dtype"):
        resolve_kv_dtype("fp8")


@pytest.mark.parametrize("heads,dh,kv,per_row", [
    # a pool row is H * Dh values padded to whole 128-lane tiles
    (4, 8, "bf16", 128 * 2),
    (4, 8, "fp32", 128 * 4),
    (4, 8, "int8", 128 + 4 * 2),       # payload row + H fp16 scales
    (4, 8, "int4", 128 + 4 * 2),       # packed payload row + scales
    (25, 64, "bf16", 1664 * 2),        # GPT-2 xl: 1,600 -> 1,664 lanes
    (16, 128, "bf16", 2048 * 2),       # whole tiles already: no padding
    (16, 128, "int4", 1024 + 16 * 2),
])
def test_kv_block_bytes_formula(heads, dh, kv, per_row):
    assert kv_block_bytes(2, heads, dh, BS, kv) == 2 * 2 * BS * per_row


@pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
def test_cache_nbytes_matches_block_accounting(kv):
    cache = PagedKVCache(toy_plan(2, 4, 8, BS, WIDTH * BS), 10, dtype=kv)
    assert cache.nbytes() == 10 * cache.bytes_per_block()
    assert cache.bytes_per_block() == kv_block_bytes(2, 4, 8, BS, kv)


def test_quant_cache_zero_init_dequantizes_to_zero():
    cache = PagedKVCache(toy_plan(1, 2, 8, BS, WIDTH * BS), 3, dtype="int8")
    payload, scales = cache.caches[0][0]
    assert payload.shape == (3 * BS, 128) and scales.shape == (3 * BS, 2)
    y = dequantize_rows(payload[:, :2 * 8].reshape(-1, 2, 8), scales,
                        "int8")
    assert (np.asarray(y) == 0.0).all()


def test_int4_cache_needs_even_head_dim():
    with pytest.raises(ValueError, match="even"):
        PagedKVCache(toy_plan(1, 2, 7, BS, WIDTH * BS), 3, dtype="int4")


# -- THE parity matrix ------------------------------------------------------


@pytest.mark.parametrize("admission", ["continuous", "static"])
@pytest.mark.parametrize("draft", [2, 4])
@pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
def test_spec_parity_matrix(model_and_params, kv, draft, admission):
    """Speculative batched serving == non-speculative one-at-a-time
    oracle at matched kv_dtype, token for token, under both admission
    policies.  int8/int4 lanes pin spec-vs-non-spec (the quantized
    cache changes numerics, so generate() is not their oracle); the
    bf16 lane additionally pins against generate() below."""
    prompts = _prompts()
    oracle = _baseline(model_and_params, kv, prompts)
    eng = _engine(model_and_params, kv_dtype=kv, draft_len=draft,
                  admission=admission)
    assert eng.generate(prompts, 10) == oracle


def test_spec_bf16_matches_generate_cache_dtype(model_and_params):
    """The dense-analogue pin: bf16-KV speculative serving ==
    generate(cache_dtype=bf16) bitwise — the serving engine IS the
    sequential decoder, drafts and all."""
    model, params = model_and_params
    prompts = _prompts(seed=7)
    eng = _engine(model_and_params, kv_dtype="bf16", draft_len=4)
    got = eng.generate(prompts, 10)
    want = [np.asarray(generate(
        model, params, np.asarray([p], np.int32), 10,
        cache_len=WIDTH * BS, cache_dtype=jnp.bfloat16))[0].tolist()
        for p in prompts]
    assert got == want


def test_spec_sampled_parity_exercises_rejection(model_and_params):
    """Seeded sampling on a random prompt: drafts get REJECTED (the
    drafter guesses greedily-plausible continuations, the target
    samples), the correction path emits the target's own token, and
    output still matches the non-spec engine exactly."""
    prompts = _prompts(seed=11)
    kw = dict(temperature=0.9, top_k=8, seeds=[5, 6, 7])
    oracle = _baseline(model_and_params, "int8", prompts, **kw)
    eng = _engine(model_and_params, kv_dtype="int8", draft_len=4)
    snap = COUNTERS.snapshot()
    got = eng.generate(prompts, 10, temperature=0.9, top_k=8,
                       seeds=[5, 6, 7])
    d = COUNTERS.delta_since(snap)
    assert got == oracle
    # rejection actually happened (else this test pins nothing)
    assert d["serve.draft_tokens"]["calls"] > \
        d.get("serve.accepted_tokens", {"calls": 0})["calls"]
    # rollback is an exact host-side rewind: no leaked blocks
    assert eng.kv.blocks_in_use == 0 and eng.kv.evictions == 0


# -- counters ---------------------------------------------------------------


def test_acceptance_counters_pinned_on_repetitive_prompt(model_and_params):
    """Greedy decode of a repeated pattern: the n-gram drafter should
    be accepted nearly every step.  Pins the exact counter identity
    (decode-emitted tokens = steps + accepted) and the campaign's
    accepted-tokens/step > 1.5 claim at test scale."""
    prompt = [7, 3, 9, 1] * 5
    n = 16
    eng = _engine(model_and_params, kv_dtype="int8", draft_len=4)
    snap = COUNTERS.snapshot()
    r = eng.submit(prompt, n)
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert r.state == FINISHED and len(r.out) == n
    steps = d["serve.decode_steps"]["calls"]
    acc = d["serve.accepted_tokens"]["calls"]
    # token 1 comes from prefill; every decode step emits its accepted
    # prefix + the target's own token, so: n - 1 == steps + accepted
    assert n - 1 == steps + acc, d
    assert acc / steps > 1.5, (acc, steps)
    assert d["serve.draft_tokens"]["calls"] >= acc
    # quantized cache -> every decode dispatch timed into kv.dequant_ms
    assert d["kv.dequant_ms"]["calls"] == steps
    assert d["kv.dequant_ms"]["bytes"] > 0


def test_dense_cache_records_no_dequant(model_and_params):
    eng = _engine(model_and_params, kv_dtype="bf16", draft_len=2)
    snap = COUNTERS.snapshot()
    eng.generate([_prompts()[0]], 6)
    d = COUNTERS.delta_since(snap)
    assert "kv.dequant_ms" not in d, d


# -- scheduler block budget (the off-by-draft regression) -------------------


def test_scheduler_reserves_speculative_tail():
    """Admission must reserve ceil((prompt + max_new + draft) / bs)
    blocks: verify writes up to draft_len candidate rows PAST the
    committed length, and those rows need real blocks, never the
    trash-padded table tail."""
    kv = PagedKVCache(toy_plan(1, 2, 8, BS, WIDTH * BS), 20, dtype="int8")
    plain = Scheduler(kv, max_batch=2, draft_len=0)
    spec = Scheduler(kv, max_batch=2, draft_len=4)
    # prompt 5 + max_new 3 = 8 tokens = exactly 2 blocks; +4 draft
    # rows spill into a third — the off-by-draft the fix reserves
    req = Request(prompt=[1] * 5, max_new_tokens=3)
    assert plain.blocks_reserved(req) == 2
    assert spec.blocks_reserved(req) == 3
    # clamped at the per-request table capacity (the engine clamps
    # per-step proposals to allocated rows, so the cap is never overrun)
    big = Request(prompt=[1] * 5, max_new_tokens=WIDTH * BS - 5)
    assert spec.blocks_reserved(big) == WIDTH


def test_spec_request_at_full_capacity_stays_exact(model_and_params):
    """A request using the engine's whole per-request token capacity
    with draft_len=4: proposals are clamped to the allocated rows
    (never the trash block), admission still succeeds, and output
    matches the non-spec oracle."""
    prompt = [5, 2] * 6                  # 12 tokens
    n = MAX_SEQ - len(prompt)            # fill the table exactly
    oracle = _baseline(model_and_params, "int8", [prompt], n=n)
    eng = _engine(model_and_params, kv_dtype="int8", draft_len=4)
    r = eng.submit(prompt, n)
    eng.run()
    assert r.state == FINISHED
    assert [r.out] == oracle
    assert eng.kv.blocks_in_use == 0


def test_spec_admission_budget_queues_not_corrupts(model_and_params):
    """Three spec requests against a pool sized so the draft tail
    forces queueing: everything completes, occupancy never exceeds
    capacity, outputs stay oracle-identical — the starvation/corruption
    regression the draft-aware reservation exists to prevent."""
    prompts = [[3, 8, 4] * 4] * 3        # 12 tokens each
    # each: ceil((12 + 8 + 4) / 4) = 6 blocks; 13 usable -> two fit
    oracle = _baseline(model_and_params, "int8", prompts, n=8)
    eng = _engine(model_and_params, kv_dtype="int8", draft_len=4,
                  num_blocks=14)
    reqs = [eng.submit(p, 8) for p in prompts]
    eng.run()
    assert all(r.state == FINISHED for r in reqs)
    assert [r.out for r in reqs] == oracle
    assert eng.peak_blocks_in_use <= eng.kv.capacity_blocks
    assert eng.kv.blocks_in_use == 0


# -- config surface ---------------------------------------------------------


def test_serving_config_block_validation():
    from deepspeed_tpu.runtime.config import DeepSpeedServingConfig

    dflt = DeepSpeedServingConfig({})
    assert dflt.kv_dtype is None and not dflt.spec_enabled
    assert dflt.to_serve_kwargs() == {
        "kv_dtype": None, "draft_len": 0, "spec_ngram": 3,
        "prefix_cache": True, "prefix_min_match_blocks": 1,
        "session_ttl_s": 120.0}

    on = DeepSpeedServingConfig({"serving": {
        "kv_dtype": "INT8",
        "speculative": {"enabled": True, "draft_len": 2, "ngram": 4}}})
    assert on.to_serve_kwargs() == {
        "kv_dtype": "int8", "draft_len": 2, "spec_ngram": 4,
        "prefix_cache": True, "prefix_min_match_blocks": 1,
        "session_ttl_s": 120.0}
    # disabled speculation maps to draft_len=0, not a missing key
    off = DeepSpeedServingConfig({"serving": {
        "speculative": {"draft_len": 2}}})
    assert off.to_serve_kwargs()["draft_len"] == 0

    with pytest.raises(ValueError, match="kv_dtype"):
        DeepSpeedServingConfig({"serving": {"kv_dtype": "fp8"}})
    with pytest.raises(ValueError, match="unknown key"):
        DeepSpeedServingConfig({"serving": {"kv_type": "int8"}})
    with pytest.raises(ValueError, match="unknown key"):
        DeepSpeedServingConfig({"serving": {
            "speculative": {"enable": True}}})
    with pytest.raises(ValueError, match="draft_len"):
        DeepSpeedServingConfig({"serving": {
            "speculative": {"draft_len": 0}}})


def test_serve_config_spec_validation():
    with pytest.raises(ValueError, match="draft_len"):
        ServeConfig(draft_len=-1)
    with pytest.raises(ValueError, match="spec_ngram"):
        ServeConfig(spec_ngram=0)
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeConfig(kv_dtype="fp8")


# -- autotune serve scope ---------------------------------------------------


def test_generate_serve_candidates_space():
    from deepspeed_tpu.runtime.autotune import generate_serve_candidates

    cands, rejected = generate_serve_candidates(head_dim=8)
    # 4 kv x 3 draft x 2 prefix modes (on with defaults / off)
    assert len(cands) == 24 and rejected == 0
    assert all(c.scope == "serve" for c in cands)
    names = {c.name for c in cands}
    assert "serve_int8_d4" in names and "serve_dense_d0" in names
    assert "serve_dense_d0_nopfx" in names
    # int4 packs two codes per byte: odd head_dim prunes the column
    cands7, rejected7 = generate_serve_candidates(head_dim=7)
    assert len(cands7) == 18 and rejected7 == 6
    assert not any("int4" in c.name for c in cands7)


def test_current_serve_candidate_and_knob_distance(model_and_params):
    from deepspeed_tpu.runtime.autotune import (current_serve_candidate,
                                                knob_distance)

    eng = _engine(model_and_params, kv_dtype="int8", draft_len=4)
    cur = current_serve_candidate(eng)
    assert cur.name == "serve_int8_d4"
    assert cur.knobs() == {
        "kv_dtype": "int8", "draft_len": 4, "prefix_cache": True,
        "min_match_blocks": 1, "session_ttl_s": 120.0}
    dense = _engine(model_and_params, draft_len=0)
    base = current_serve_candidate(dense)
    assert base.knobs() == {
        "kv_dtype": "dense", "draft_len": 0, "prefix_cache": True,
        "min_match_blocks": 1, "session_ttl_s": 120.0}
    assert knob_distance(cur, cur) == 0
    assert knob_distance(cur, base) == 2          # kv + draft differ


def test_serve_fingerprint_keys_on_kv_dtype(model_and_params):
    from deepspeed_tpu.runtime.autotune import (fingerprint_diff,
                                                serve_fingerprint)

    a = serve_fingerprint(_engine(model_and_params, kv_dtype="int8"))
    b = serve_fingerprint(_engine(model_and_params, kv_dtype="bf16"))
    assert a["digest"] != b["digest"]
    assert any("kv_dtype" in p for p in fingerprint_diff(a, b))
    # same engine config -> identical fingerprint (cacheable)
    c = serve_fingerprint(_engine(model_and_params, kv_dtype="int8"))
    assert a == c


# -- serve_bench ------------------------------------------------------------


def test_percentile_nearest_rank():
    """The pinned convention: smallest sample with >= q% of the
    distribution at or below it — always an OBSERVED latency, never an
    interpolated one."""
    import serve_bench

    p = serve_bench._percentile
    assert p([4, 1, 3, 2], 50) == 2
    assert p([4, 1, 3, 2], 100) == 4
    assert p(list(range(1, 101)), 99) == 99
    assert p([7.5], 99) == 7.5
    assert p([1, 2], 1) == 1          # ceil clamps to the first sample
    assert p([], 50) is None


def test_serve_bench_dry_spec_lane():
    """tools/serve_bench.py --dry-run --spec (tier-1 so the lane cannot
    rot): the (kv_dtype x draft_len) sweep completes, spec lanes
    accept, the bf16/dense lanes pin bitwise against generate(), and
    the equal-pool resident-session pair separates."""
    import serve_bench

    result = serve_bench.run_dry_spec(record=False)
    assert result["resident_sessions"]["resident_ratio"] > 1.0
    assert set(result["spec_speedup_tokens_per_sec"]) == \
        {"dense", "bf16", "int8", "int4"}
