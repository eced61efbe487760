"""Block-level prefix cache, pinned KV sessions, and the fleet router
(deepspeed_tpu/serving, PR 19).

THE acceptance pin: greedy serving is bitwise-identical with the
prefix cache on vs off — across every kv storage mode (dense fp32,
bf16, int8, int4) and with speculative decoding — because aliasing
full blocks changes WHERE prompt K/V rows live, never their contents
(serving/programs.py is untouched on the read path).  Everything else
here is allocator book-keeping: refcounts, LRU parking, copy-on-write,
session pins, and least-loaded dispatch."""

import io
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

from deepspeed_tpu.models import GPT, gpt2_config
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import (ERROR, FINISHED, FleetRouter,
                                   PagedKVCache, ServeConfig, ServeEngine,
                                   ServeProgramBuilder, ServeSchedule,
                                   build_fleet)
from toy_plans import toy_plan

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
sys.path.insert(0, TOOLS)

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
    base = dict(block_size=BS, num_blocks=40, max_batch=4,
                prefill_chunk=8, max_seq_len=MAX_SEQ)
    base.update(over)
    return ServeConfig(**base)


# ONE compiled program set per (kv wire-or-dense, draft_len) shared by
# every engine in the module — the prefix cache is host-side allocator
# state, so cache-on and cache-off engines share a program pair (the
# exactness claim, stated in compiler terms).
_PROGRAMS = {}


def _engine(model_and_params, **over):
    from deepspeed_tpu.serving.kv_cache import resolve_kv_dtype

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


def _kv(**over):
    base = dict(num_blocks=6)
    base.update(over)
    return PagedKVCache(toy_plan(1, 2, 4, BS, 8 * BS), **base)


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


# -- allocator edge cases (the free/alloc regression lane) ------------------


def test_free_is_idempotent_and_unknown_rid_is_a_noop():
    """Double-free and unknown-rid free return 0 and change nothing —
    the scheduler's finish path and a shed race can both reach free()
    for a request that already released."""
    kv = _kv()
    snap = COUNTERS.snapshot()
    assert kv.capacity_blocks == 5
    table = kv.alloc("a", 3)
    assert table is not None and kv.blocks_in_use == 3
    assert kv.free_blocks == 2
    assert kv.free("a") == 3
    assert kv.free("a") == 0          # second free: gone, not an error
    assert kv.free("ghost") == 0      # never-allocated rid
    assert kv.blocks_in_use == 0 and kv.free_blocks == 5
    assert kv.evictions == 0
    d = COUNTERS.delta_since(snap)
    assert "kv.evictions" not in d    # natural frees never count


def test_alloc_exactly_exhausting_the_pool():
    kv = _kv()
    snap = COUNTERS.snapshot()
    table = kv.alloc("big", 5)        # every allocatable block
    assert table is not None
    assert kv.blocks_in_use == 5 and kv.free_blocks == 0
    assert kv.alloc("late", 1) is None          # pool dry -> None, not raise
    with pytest.raises(ValueError, match="already holds"):
        kv.alloc("big", 1)
    with pytest.raises(ValueError, match="table width"):
        kv.alloc("wide", kv.table_width + 1)
    assert kv.free("big") == 5
    assert kv.blocks_in_use == 0 and kv.free_blocks == 5
    # forced reclaim (shed path) DOES count, once per released block
    kv.alloc("shed", 2)
    assert kv.free("shed", evicted=True) == 2
    assert kv.evictions == 2
    d = COUNTERS.delta_since(snap)
    assert d["kv.evictions"]["calls"] == 2


def test_alloc_when_matched_blocks_are_the_lru_residents():
    """The admission check must not double-count a matched block as
    BOTH the shared prefix and reclaimable capacity: with the free
    list dry and every LRU resident matched, an allocation needing
    fresh tail blocks must return None (pool intact) — not drain an
    empty pool mid-allocation."""
    kv = _kv(num_blocks=7)            # 6 usable
    toks = _tokens(12)
    hashes = kv.prefix_hashes(toks)
    kv.alloc("r1", 3)
    kv.register_prefix("r1", hashes)
    kv.free("r1")                     # LRU: 3 parked, free list: 3
    kv.alloc("hold", 2)               # free list: 1
    m = kv.match_prefix(hashes)
    assert len(m) == 3
    # fresh share = 2, but real capacity = 1 free + (3 LRU - 3 matched)
    assert kv.alloc("r2", 5, shared=m) is None
    # same overlap through the whole-prompt-cached adopt path
    assert kv.alloc("r2", 5, shared=m, privatize_last=True) is None
    # the refused allocation touched nothing: blocks stay matchable
    assert kv.blocks_in_use == 2 and kv.cached_blocks == 3
    assert kv.match_prefix(hashes) == m
    kv.free("hold")                   # free list: 3 -> now it fits
    assert kv.alloc("r2", 5, shared=m) is not None
    assert kv.blocks_of("r2")[:3] == m
    assert kv.blocks_in_use == 5


# -- prefix cache: hashing, refcounts, LRU, eviction, COW -------------------


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


def test_prefix_hashes_full_blocks_only_and_salt_matters():
    kv = _kv()
    toks = _tokens(14)
    hashes = kv.prefix_hashes(toks)
    assert len(hashes) == 14 // BS    # the partial tail is never hashed
    assert hashes == kv.prefix_hashes(toks)[:3]
    # the chain binds position: a different FIRST block changes all
    other = kv.prefix_hashes([t ^ 1 for t in toks[:4]] + toks[4:])
    assert all(a != b for a, b in zip(hashes, other))
    # a different salt (model / storage mode) never cross-matches
    salted = _kv(prefix_salt="other-model")
    assert kv.prefix_hashes(toks) != salted.prefix_hashes(toks)
    # disabled cache: no hashing, no matching
    off = _kv(prefix_cache=False)
    assert off.prefix_hashes(toks) == []
    assert off.match_prefix(hashes) == []


def test_register_match_lru_park_and_refcounted_aliasing():
    kv = _kv()
    toks = _tokens(12)
    hashes = kv.prefix_hashes(toks)
    kv.alloc("r1", 3)
    blocks = kv.blocks_of("r1")
    assert kv.register_prefix("r1", hashes) == 3
    kv.free("r1")
    # registered blocks PARK in the LRU: not in use, still matchable,
    # and allocatable the moment the free list runs dry
    assert kv.blocks_in_use == 0 and kv.free_blocks == 5
    assert kv.cached_blocks == 3
    assert kv.match_prefix(hashes) == blocks
    # two live requests alias the same physical blocks
    m = kv.match_prefix(hashes)
    kv.alloc("r2", 4, shared=m)
    kv.alloc("r3", 3, shared=m)
    assert kv.blocks_of("r2")[:3] == blocks == kv.blocks_of("r3")
    assert kv.blocks_in_use == 4      # 3 shared + r2's fresh tail block
    kv.free("r2")
    assert kv.blocks_in_use == 3      # r3 still holds the shared three
    kv.free("r3")
    assert kv.blocks_in_use == 0 and kv.cached_blocks == 3


def test_min_match_blocks_threshold():
    kv = _kv(min_match_blocks=2)
    toks = _tokens(12)
    hashes = kv.prefix_hashes(toks)
    kv.alloc("r1", 3)
    kv.register_prefix("r1", hashes)
    kv.free("r1")
    assert kv.match_prefix(hashes[:1]) == []   # 1 block < threshold
    assert len(kv.match_prefix(hashes)) == 3


def test_lru_eviction_under_pressure_oldest_first():
    """An allocation the free list cannot cover reclaims refcount-0
    cached blocks oldest-first, deregistering their hashes — and never
    touches a live holder."""
    kv = _kv()
    toks = _tokens(12)
    hashes = kv.prefix_hashes(toks)
    kv.alloc("r1", 3)
    blocks = kv.blocks_of("r1")
    kv.register_prefix("r1", hashes)
    kv.free("r1")                     # 3 parked, 2 on the free list
    snap = COUNTERS.snapshot()
    assert kv.alloc("r2", 4) is not None   # 2 free + 2 evicted
    assert kv.prefix_evictions == 2
    assert COUNTERS.delta_since(snap)["kv.prefix_evictions"]["calls"] == 2
    # free() parks blocks last-first, so the chain HEAD survives longest
    assert kv.cached_blocks == 1
    assert kv.match_prefix(hashes) == blocks[:1]


def test_whole_prompt_cached_adopt_vs_copy_on_write():
    """The one write that can land in a shared block — the final
    prompt token's recompute on a full block-aligned hit: a refcount-0
    block is adopted in place (keeps its hash), a live-shared block is
    row-copied to a private block first."""
    kv = _kv(num_blocks=10)
    toks = _tokens(12)
    hashes = kv.prefix_hashes(toks)
    kv.alloc("r1", 3)
    blocks = kv.blocks_of("r1")
    kv.register_prefix("r1", hashes)
    kv.free("r1")
    # adopt: sole (parked) holder, no copy, hash preserved
    m = kv.match_prefix(hashes)
    kv.alloc("r2", 4, shared=m, privatize_last=True)
    assert kv.blocks_of("r2")[:3] == blocks
    assert kv.cow_copies == 0
    assert kv.match_prefix(hashes) == blocks
    # COW: r2 is live, so an identical admission must not write into
    # the block r2 attends through
    snap = COUNTERS.snapshot()
    kv.alloc("r3", 4, shared=kv.match_prefix(hashes), privatize_last=True)
    assert kv.cow_copies == 1
    assert kv.blocks_of("r3")[2] != blocks[2]   # private last block
    assert kv.blocks_of("r3")[:2] == blocks[:2]
    d = COUNTERS.delta_since(snap)
    assert d["kv.cow_copies"]["calls"] == 1
    assert d["kv.cow_copies"]["bytes"] == kv.bytes_per_block()
    kv.free("r2")
    kv.free("r3")
    assert kv.blocks_in_use == 0


# -- THE acceptance pin: bitwise parity, cache on vs off --------------------


def _family(seed=0):
    """Shared-prefix prompts: a repetitive 12-token base (so draft>0
    lanes actually accept) + two tails, plus an exact repeat of the
    first prompt (the whole-prompt-cached adopt/COW admission)."""
    rs = np.random.RandomState(seed)
    base = rs.randint(0, VOCAB, (3,)).tolist() * 4
    t0 = rs.randint(0, VOCAB, (4,)).tolist()
    t1 = rs.randint(0, VOCAB, (4,)).tolist()
    return [base + t0, base + t1, base + t0]


@pytest.mark.parametrize("kv", [None, "bf16", "int8", "int4"])
@pytest.mark.parametrize("draft", [0, 4])
def test_prefix_parity_matrix(model_and_params, kv, draft):
    """Greedy serving is bitwise-identical with the prefix cache on vs
    off, at every kv storage mode and with speculative decoding — and
    the cache-on engine really did alias blocks (a vacuous pass where
    nothing hit would prove nothing)."""
    prompts = _family(seed=7)
    on = _engine(model_and_params, kv_dtype=kv, draft_len=draft)
    off = _engine(model_and_params, kv_dtype=kv, draft_len=draft,
                  prefix_cache=False)
    snap = COUNTERS.snapshot()
    outs_on, outs_off = [], []
    for p in prompts:               # sequential, so later prompts HIT
        r = on.submit(p, 8)
        on.run()
        outs_on.append(r.out)
    d = COUNTERS.delta_since(snap)
    snap = COUNTERS.snapshot()
    for p in prompts:
        r = off.submit(p, 8)
        off.run()
        outs_off.append(r.out)
    assert outs_on == outs_off
    assert d["kv.prefix_hits"]["calls"] >= 2          # tail + repeat hits
    assert d["kv.prefix_hit_tokens"]["bytes"] > 0
    assert "kv.prefix_hits" not in COUNTERS.delta_since(snap)


def test_prefix_hit_counters_and_prefill_skip_pinned(model_and_params):
    """Exact counter semantics on a hand-computed admission sequence:
    prompt lengths chosen so every quantity is a small integer."""
    base = _tokens(12, seed=21)                # 3 full blocks
    eng = _engine(model_and_params)
    r1 = eng.submit(base, 4)
    eng.run()
    # r2 shares the first TWO blocks (8 tokens), then diverges
    snap = COUNTERS.snapshot()
    r2 = eng.submit(base[:8] + _tokens(4, seed=22), 4)
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert r2.prefix_cached_tokens == 8
    assert d["kv.prefix_hits"] == {"calls": 1, "bytes": 2}, d
    assert d["kv.prefix_hit_tokens"]["bytes"] == 8
    # prefill computed ONLY the 4 uncached tokens, in one chunk
    assert d["serve.prefill_chunks"] == {"calls": 1, "bytes": 4}, d
    # r3: the whole prompt is cached -> only the final token recomputes
    snap = COUNTERS.snapshot()
    r3 = eng.submit(base, 4)
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert r3.prefix_cached_tokens == 11       # min(12, len - 1)
    assert d["kv.prefix_hits"] == {"calls": 1, "bytes": 3}, d
    assert d["serve.prefill_chunks"] == {"calls": 1, "bytes": 1}, d
    assert r3.out == r1.out
    assert eng.kv.blocks_in_use == 0


def test_live_shared_block_goes_copy_on_write_in_engine(model_and_params):
    """An identical prompt admitted WHILE the first holder still
    decodes: the final-token write must not land in the live-shared
    block — and both outputs stay oracle-identical."""
    base = _tokens(12, seed=23)
    eng = _engine(model_and_params)
    ra = eng.submit(base, 8)
    eng.step()                        # chunk 1 (8 tokens)
    eng.step()                        # chunk 2 (4 tokens) -> registered
    snap = COUNTERS.snapshot()
    rb = eng.submit(base, 8)          # ra still holds its blocks
    eng.run()
    d = COUNTERS.delta_since(snap)
    assert d["kv.cow_copies"]["calls"] == 1
    assert rb.prefix_cached_tokens == 11
    assert ra.out == rb.out
    off = _engine(model_and_params, prefix_cache=False)
    assert ra.out == off.generate([base], 8)[0]


# -- pinned sessions --------------------------------------------------------


def test_session_pin_second_turn_prefills_only_new_tokens(
        model_and_params):
    clk = _Clock()
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg(), programs=_PROGRAMS[
        ("dense", 0)], clock=clk)
    p1 = _tokens(10, seed=31)
    r1 = eng.submit(p1, 5, session_id="chat")
    eng.run()
    hist = p1 + r1.out
    assert eng.resident_sessions == 1
    # the pin holds every block the 15-token history needs
    assert eng.kv.blocks_in_use == -(-len(hist) // BS)
    p2 = hist + _tokens(4, seed=32)
    snap = COUNTERS.snapshot()
    r2 = eng.submit(p2, 5, session_id="chat")
    eng.run()
    d = COUNTERS.delta_since(snap)
    # the final emitted token's row was never written -> re-prefill
    # starts there: 19 - 14 = 5 tokens, one chunk
    assert r2.prefix_cached_tokens == len(hist) - 1
    assert d["serve.prefill_chunks"] == {"calls": 1, "bytes": 5}, d
    assert d["kv.prefix_hit_tokens"]["bytes"] == len(hist) - 1
    assert d["kv.session_pins"]["calls"] == 1       # turn 2 re-pinned
    off = _engine(model_and_params, prefix_cache=False)
    assert r2.out == off.generate([p2], 5)[0]
    assert eng.resident_sessions == 1
    # TTL expiry releases the pin; registered blocks stay matchable
    clk.t += eng.config.session_ttl_s + 1
    eng.step()
    assert eng.resident_sessions == 0
    assert eng.kv.blocks_in_use == 0
    assert eng.kv.cached_blocks > 0
    assert eng.release_session("chat") is False     # already gone


def test_pin_adopted_turns_publish_no_prefix_blocks(model_and_params):
    """A warm turn's prefill attends over the pin's decode-written
    rows, which are NOT bitwise-pinned against a cold recompute — so
    none of its blocks may be published under token-only chain hashes.
    Third parties must match only the turn-1 (pure-prefill) blocks."""
    eng = _engine(model_and_params)
    p1 = _tokens(10, seed=37)                  # registers 2 full blocks
    r1 = eng.submit(p1, 5, session_id="pub")
    eng.run()
    assert eng.kv.cached_blocks == 2
    hist = p1 + r1.out                         # 15 tokens
    p2 = hist + _tokens(6, seed=38)            # 21 tokens, 5 full blocks
    r2 = eng.submit(p2, 4, session_id="pub")
    eng.run()
    assert r2.prefix_cached_tokens == len(hist) - 1
    assert r2.block_hashes == []               # adopted -> never publish
    # block 4 (tokens 16..19) was prefilled ATTENDING over the pin's
    # decode rows; with the old registration it became matchable
    assert eng.kv.cached_blocks == 2
    h2 = eng.kv.prefix_hashes(p2)
    assert len(eng.kv.match_prefix(h2)) == 2   # only turn-1's blocks


def test_session_edited_history_falls_back_loudly(model_and_params):
    """A turn whose prompt is NOT a prefix-extension of the pinned
    history (user edited the conversation) releases the pin and falls
    back to chain-hash matching — correctness never depends on the
    session being honest."""
    eng = _engine(model_and_params)
    p1 = _tokens(10, seed=33)
    r1 = eng.submit(p1, 5, session_id="edit")
    eng.run()
    edited = [p1[0] ^ 1] + p1[1:] + r1.out + _tokens(3, seed=34)
    r2 = eng.submit(edited, 5, session_id="edit")
    eng.run()
    assert r2.prefix_cached_tokens == 0       # first block already differs
    off = _engine(model_and_params, prefix_cache=False)
    assert r2.out == off.generate([edited], 5)[0]
    assert eng.resident_sessions == 1         # re-pinned on the NEW history


def test_session_pressure_release_frees_pins_for_waiting_requests(
        model_and_params):
    """A waiting request always outranks a resident session: when the
    shortfall is blocks (not slots), pins release oldest-first."""
    eng = _engine(model_and_params, num_blocks=9)   # 8 usable
    p1 = _tokens(10, seed=35)
    eng.submit(p1, 6, session_id="s")
    eng.run()
    assert eng.resident_sessions == 1
    assert eng.kv.blocks_in_use == 4                # ceil(16 / 4) pinned
    big = _tokens(14, seed=36)                      # needs 6 of 8 blocks
    r = eng.submit(big, 10)
    eng.run()
    assert r.state == FINISHED
    assert eng.resident_sessions == 0               # pin was sacrificed
    assert r.out == _engine(model_and_params,
                            prefix_cache=False).generate([big], 10)[0]


# -- fleet router -----------------------------------------------------------


def test_build_fleet_shares_programs_and_validates(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="replicas"):
        build_fleet(model, params, _cfg(), replicas=0)
    engines = build_fleet(model, params, _cfg(), replicas=3,
                          programs=_PROGRAMS[("dense", 0)])
    assert len(engines) == 3
    assert all(e.programs is engines[0].programs for e in engines)
    assert engines[1].kv is not engines[0].kv       # own pool each
    with pytest.raises(ValueError, match="at least one"):
        FleetRouter([])
    with pytest.raises(ValueError, match="queue_limit"):
        FleetRouter(engines, queue_limit=0)
    with pytest.raises(ValueError, match="affinity_cap"):
        FleetRouter(engines, affinity_cap=0)
    for e in engines:
        e.close()


def test_router_least_loaded_dispatch_and_counters(model_and_params):
    model, params = model_and_params
    engines = build_fleet(model, params, _cfg(), replicas=2,
                          programs=_PROGRAMS[("dense", 0)])
    router = FleetRouter(engines, queue_limit=4)
    snap = COUNTERS.snapshot()
    pa, pb = _tokens(6, seed=41), _tokens(9, seed=42)
    r1 = router.submit(pa, 4)
    r2 = router.submit(pb, 4)       # replica 0 now has queue depth 1
    assert (r1.replica, r2.replica) == (0, 1)
    router.run()
    assert r1.state == FINISHED and r2.state == FINISHED
    d = COUNTERS.delta_since(snap)
    assert d["router.dispatches"]["calls"] == 2
    assert "router.spills" not in d and "router.shed" not in d
    off = _engine(model_and_params, prefix_cache=False)
    assert r1.out == off.generate([pa], 4)[0]
    assert r2.out == off.generate([pb], 4)[0]
    router.close()


def test_router_session_affinity_beats_load(model_and_params):
    """A pinned session's blocks are resident on exactly one replica —
    its next turn MUST land there even when another replica is
    emptier, and the warm turn really does skip the history."""
    model, params = model_and_params
    engines = build_fleet(model, params, _cfg(), replicas=2,
                          programs=_PROGRAMS[("dense", 0)])
    router = FleetRouter(engines, queue_limit=4)
    p1 = _tokens(10, seed=43)
    r1 = router.submit(p1, 5, session_id="aff")
    router.run()
    home = r1.replica
    assert engines[home].resident_sessions == 1
    assert engines[home].kv.blocks_in_use > 0       # the pin: home is
    other = router.submit(_tokens(6, seed=44), 4)   # now the LOADED one
    assert other.replica != home
    hist = p1 + r1.out
    r2 = router.submit(hist + _tokens(4, seed=45), 5, session_id="aff")
    assert r2.replica == home
    router.run()
    assert r2.prefix_cached_tokens == len(hist) - 1
    router.close()


def test_router_affinity_dropped_when_pin_released(model_and_params):
    """Affinity must not outlive the pin: once the engine released the
    session (TTL here; pressure/error chains behave the same), the next
    turn routes by load and the stale mapping is dropped — a dead
    session must not keep hammering one replica forever."""
    model, params = model_and_params
    clk = _Clock()
    engines = build_fleet(model, params, _cfg(), replicas=2,
                          programs=_PROGRAMS[("dense", 0)], clock=clk)
    router = FleetRouter(engines, queue_limit=4)
    p1 = _tokens(10, seed=61)
    r1 = router.submit(p1, 5, session_id="aff")
    router.run()
    home = r1.replica
    other = 1 - home
    assert engines[home].resident_sessions == 1
    # make home the LOADED replica: only stale affinity would pick it
    busy = engines[home].submit(_tokens(8, seed=62), 12)
    engines[home].step()
    assert (engines[home].kv.blocks_in_use
            > engines[other].kv.blocks_in_use)
    clk.t += engines[home].config.session_ttl_s + 1
    engines[home].step()                       # TTL releases the pin
    assert engines[home].resident_sessions == 0
    r2 = router.submit(_tokens(6, seed=63), 4, session_id="aff")
    assert r2.replica == other
    assert router._session_replica["aff"] == other
    router.run()
    assert all(r.state == FINISHED for r in (r1, busy, r2))
    router.close()


def test_router_affinity_map_swept_at_cap(model_and_params):
    """The affinity map is bounded: overflowing `affinity_cap` sweeps
    every mapping whose session is no longer active on its replica,
    so many distinct one-shot session ids cannot grow it forever."""
    model, params = model_and_params
    engines = build_fleet(model, params, _cfg(), replicas=2,
                          programs=_PROGRAMS[("dense", 0)])
    router = FleetRouter(engines, queue_limit=4, affinity_cap=1)
    ra = router.submit(_tokens(6, seed=64), 3, session_id="a")
    router.run()
    assert engines[ra.replica].release_session("a")   # chain abandoned
    rb = router.submit(_tokens(6, seed=65), 3, session_id="b")
    assert set(router._session_replica) == {"b"}      # dead "a" swept
    router.run()
    assert rb.state == FINISHED
    router.close()


def test_router_submit_is_thread_safe(model_and_params):
    """Concurrent frontend submits: counters stay consistent and
    concurrent first turns of ONE session land on one replica (the
    race the dispatch mutex exists to close)."""
    model, params = model_and_params
    engines = build_fleet(model, params, _cfg(), replicas=2,
                          programs=_PROGRAMS[("dense", 0)])
    router = FleetRouter(engines, queue_limit=64)
    reqs, errs = [], []
    guard = threading.Lock()

    def frontend(k):
        try:
            for _ in range(4):
                r = router.submit(_tokens(5, seed=70 + k), 2,
                                  session_id="t" if k % 2 == 0 else None)
                with guard:
                    reqs.append(r)
        except Exception as e:                 # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=frontend, args=(k,))
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert router.dispatched == len(reqs) == 32
    homes = {r.replica for r in reqs if r.session_id == "t"}
    assert len(homes) == 1
    router.run()
    assert all(r.state == FINISHED for r in reqs)
    router.close()


def test_router_spill_and_shed_at_saturation(model_and_params):
    model, params = model_and_params
    engines = build_fleet(model, params, _cfg(), replicas=2,
                          programs=_PROGRAMS[("dense", 0)])
    router = FleetRouter(engines, queue_limit=1)
    # replica 1 holds live blocks (mid-decode), so replica 0 is the
    # least-loaded pick throughout
    busy = engines[1].submit(_tokens(8, seed=46), 12)
    engines[1].step()
    assert engines[1].kv.blocks_in_use > 0
    snap = COUNTERS.snapshot()
    ra = router.submit(_tokens(5, seed=47), 4)      # -> 0, queue full
    rb = router.submit(_tokens(5, seed=48), 4)      # 0 full -> SPILL to 1
    rc = router.submit(_tokens(5, seed=49), 4)      # both full -> SHED
    assert (ra.replica, rb.replica) == (0, 1)
    assert router.spilled == 1 and router.shed == 1
    assert rc.state == ERROR and "saturated" in rc.error
    assert getattr(rc, "replica", None) is None     # never enqueued
    d = COUNTERS.delta_since(snap)
    assert d["router.spills"]["calls"] == 1
    assert d["router.shed"]["calls"] == 1
    assert d["router.dispatches"]["calls"] == 2
    router.run()
    assert all(r.state == FINISHED for r in (busy, ra, rb))
    assert rc.state == ERROR                        # shed stays shed
    router.close()


# -- the fleet bench lane (tier-1 so the campaign cannot rot) ---------------


def test_serve_bench_fleet_dry_run():
    """tools/serve_bench.py --dry-run --fleet: the deterministic
    halves of every headline claim — bitwise cache-on == cache-off
    through 1- and 2-replica fleets, a nonzero hit rate, session pins
    engaging, warm turns computing strictly fewer prefill tokens than
    cold — asserted inside run_dry_fleet itself."""
    import serve_bench

    result = serve_bench.run_dry_fleet(record=False)
    assert result["lanes"]["fleet_r2"]["prefix_hit_rate"] > 0.25
    ses = result["session"]
    assert ses["warm_prefill_tokens"] < ses["cold_prefill_tokens"]
    assert ses["session_pins"] > 0


def test_bench_gate_prefix_hit_rate_floor(tmp_path):
    """tools/bench_gate.py --min-prefix-hit-rate gates the committed
    fleet artifact on its CLAIM (platform-independent), with
    --require-tpu restoring the hardware check."""
    art = tmp_path / "bench.json"
    art.write_text(json.dumps({
        "metric": "serve_fleet_bench", "value": 0.61,
        "platform": "cpu-smoke"}) + "\n")
    gate = os.path.join(TOOLS, "bench_gate.py")

    def run(*extra):
        return subprocess.run([sys.executable, gate, str(art), *extra],
                              capture_output=True, text=True)

    ok = run("--min-prefix-hit-rate", "0.5")
    assert ok.returncode == 0 and "0.610" in ok.stdout
    assert run("--min-prefix-hit-rate", "0.7").returncode == 1
    assert run("--min-prefix-hit-rate", "0.5",
               "--require-tpu").returncode == 1     # cpu-smoke artifact
    assert run().returncode == 1                    # default mode: hardware


# -- config + report surfaces -----------------------------------------------


def test_fleet_and_prefix_config_blocks():
    from deepspeed_tpu.runtime.config import DeepSpeedServingConfig

    dflt = DeepSpeedServingConfig({})
    assert dflt.to_fleet_kwargs() == {
        "replicas": 1, "queue_limit": 64, "session_affinity": True}

    on = DeepSpeedServingConfig({"serving": {
        "prefix_cache": {"enabled": False, "min_match_blocks": 2,
                         "session_ttl_s": 30},
        "fleet": {"replicas": 4, "queue_limit": 8,
                  "session_affinity": False}}})
    assert on.to_fleet_kwargs() == {
        "replicas": 4, "queue_limit": 8, "session_affinity": False}
    sk = on.to_serve_kwargs()
    assert sk["prefix_cache"] is False
    assert sk["prefix_min_match_blocks"] == 2
    assert sk["session_ttl_s"] == 30.0

    with pytest.raises(ValueError, match="replicas"):
        DeepSpeedServingConfig({"serving": {"fleet": {"replicas": 0}}})
    with pytest.raises(ValueError, match="unknown key"):
        DeepSpeedServingConfig({"serving": {"fleet": {"qlimit": 2}}})
    with pytest.raises(ValueError, match="min_match_blocks"):
        DeepSpeedServingConfig({"serving": {
            "prefix_cache": {"min_match_blocks": 0}}})
    with pytest.raises(ValueError, match="session_ttl_s"):
        DeepSpeedServingConfig({"serving": {
            "prefix_cache": {"session_ttl_s": 0}}})
    with pytest.raises(ValueError, match="unknown key"):
        DeepSpeedServingConfig({"serving": {"prefix_cache": {"ttl": 1}}})


def test_serve_config_prefix_validation():
    with pytest.raises(ValueError, match="prefix_min_match_blocks"):
        ServeConfig(prefix_min_match_blocks=0)
    with pytest.raises(ValueError, match="session_ttl_s"):
        ServeConfig(session_ttl_s=0)


def test_env_report_serving_section(model_and_params):
    from deepspeed_tpu.env_report import serving_report

    buf = io.StringIO()
    serving_report(out=buf)
    s = buf.getvalue()
    assert "DeepSpeed-TPU serving status:" in s
    assert "paged attention kernel" in s
    assert "prefix cache" in s and "enabled" in s
    assert "resident sessions" in s and "no live engine" in s

    eng = _engine(model_and_params)
    r = eng.submit(_tokens(6, seed=51), 3, session_id="rep")
    eng.run()
    assert r.state == FINISHED
    buf = io.StringIO()
    serving_report(out=buf, engine=eng)
    s = buf.getvalue()
    assert any(ln.startswith("resident sessions") and ln.endswith(" 1")
               for ln in s.splitlines())
    assert "dense" in s
