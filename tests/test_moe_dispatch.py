"""Fused sort-based MoE dispatch + the explicit expert a2a wire
(moe/dispatch.py, the `"comm": {"moe": ...}` block).

Covers the PR-contract matrix: dense-vs-sorted parity (top_k x capacity
x train/eval x gate noise), dropless exactly-once accounting, the
capacity-ceil boundary regression, explicit-wire parity on flat and
factored meshes, moe.* counters pinned byte-exact against the static
A2APlan, config-time rejection of invalid combinations, and the
engine-level dryrun pinning loss parity with the dense path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import make_mesh
from deepspeed_tpu.moe import MoE, MoEConfig, top_k_gating
from deepspeed_tpu.moe import dispatch as dsp
from deepspeed_tpu.monitor.counters import COUNTERS


def _moe(E=4, k=2, factor=2.0, noise=0.0, min_cap=1, d=8, f=16):
    return MoE(MoEConfig(d_model=d, d_ff=f, num_experts=E, top_k=k,
                         capacity_factor=factor, min_capacity=min_cap,
                         noisy_gate_std=noise))


def _moe_deltas(snap):
    jax.effects_barrier()
    return {k: v for k, v in COUNTERS.delta_since(snap).items()
            if k.startswith("moe.")}


# ---------------------------------------------------------------------------
# routing core
# ---------------------------------------------------------------------------

def test_routing_positions_are_int32_and_exact():
    # many tokens to one expert: queue positions must be an exact
    # integer permutation (the seed's fp32 cumsum relied on fp32
    # integer exactness, which dies past 2^24 tokens)
    probs = jnp.tile(jnp.asarray([[0.9, 0.1]]), (300, 1))
    eidx, gate, pos, keep, aux = dsp.topk_routing(probs, 1, 300)
    assert pos.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(pos[0]), np.arange(300))
    assert bool(keep.all())


def test_routing_matches_dense_gating_queue_order():
    # dense one-hot gating (built on the same core) drops EXACTLY the
    # tokens past each expert's capacity, earlier rounds queued first
    logits = jnp.asarray(np.random.RandomState(3).randn(24, 4),
                         jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    eidx, gate, pos, keep, _ = dsp.topk_routing(probs, 2, 3)
    # per expert: kept positions are 0..min(count,3)-1 with no gaps
    e = np.asarray(eidx).reshape(-1)
    p = np.asarray(pos).reshape(-1)
    kp = np.asarray(keep).reshape(-1)
    for ex in range(4):
        mine = p[e == ex]
        np.testing.assert_array_equal(np.sort(mine), np.arange(len(mine)))
        assert (p[(e == ex) & kp] < 3).all()


def test_capacity_uses_ceiling_not_truncation():
    # S=6, E=4, factor=1.25, k=1: 1.875 slots/expert — the seed's int()
    # gave 1 and dropped the second token of a balanced pair even at
    # factor >= 1.0; ceil gives 2
    m = _moe(E=4, k=1, factor=1.25, min_cap=1)
    assert m.capacity(6, train=True) == 2
    # exact products stay exact (no epsilon drift)
    m2 = _moe(E=8, k=2, factor=1.25, min_cap=1)
    assert m2.capacity(32, train=True) == 10
    assert m2.capacity(32, train=False) == 16  # eval factor 2.0
    # min_capacity still floors
    assert _moe(E=4, k=1, factor=1.25, min_cap=4).capacity(6, True) == 4


def test_capacity_boundary_no_longer_drops_balanced_tokens():
    # 6 tokens, 4 experts, top-1, factor 1.25: a 2-2-1-1 routing needs
    # 2 slots on the busy experts; the truncated capacity (1) dropped
    # one token from each
    logits = jnp.asarray([[9, 0, 0, 0], [9, 0, 0, 0], [0, 9, 0, 0],
                          [0, 9, 0, 0], [0, 0, 9, 0], [0, 0, 0, 9]],
                         jnp.float32)
    m = _moe(E=4, k=1, factor=1.25, min_cap=1)
    cap = m.capacity(6, train=True)
    combine, dispatch, _ = top_k_gating(logits, 1, cap)
    # every token keeps a nonzero combine weight — nothing dropped
    assert (np.asarray(combine).sum((1, 2)) > 0).all()


# ---------------------------------------------------------------------------
# dense vs sorted parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("factor,min_cap", [(0.5, 1), (4.0, 4)])
@pytest.mark.parametrize("train", [True, False])
@pytest.mark.parametrize("noise", [0.0, 1e-2])
def test_dense_vs_sorted_parity(k, factor, min_cap, train, noise):
    moe = _moe(E=4, k=k, factor=factor, noise=noise, min_cap=min_cap)
    params = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 8))
    rng = jax.random.PRNGKey(2) if (train and noise > 0) else None
    y_d, aux_d = moe(params, x, rng=rng, train=train)
    with dsp.moe_wire(dispatch="sorted"):
        y_s, aux_s = moe(params, x, rng=rng, train=train)
    # routing is IDENTICAL (shared core); movement differs only by
    # multiply-accumulate fusion in the dense einsums -> one-ulp-level
    # agreement, exact aux
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_s),
                               rtol=2e-6, atol=2e-7)
    assert float(aux_d) == float(aux_s)


def test_dense_vs_sorted_drop_the_same_tokens():
    # tight capacity: both engines must zero exactly the same tokens
    logits_x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 8))
    moe = _moe(E=2, k=1, factor=0.25, min_cap=1)
    params = moe.init(jax.random.PRNGKey(0))
    y_d, _ = moe(params, logits_x, train=True)
    with dsp.moe_wire(dispatch="sorted"):
        y_s, _ = moe(params, logits_x, train=True)
    dropped_d = np.asarray(jnp.abs(y_d).sum(-1) == 0)
    dropped_s = np.asarray(jnp.abs(y_s).sum(-1) == 0)
    np.testing.assert_array_equal(dropped_d, dropped_s)
    assert dropped_d.any()  # the case exercises real drops


def test_sorted_grads_match_dense():
    moe = _moe(E=4, k=2, factor=2.0, noise=1e-2)
    params = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8))

    def loss(p, mode):
        with dsp.moe_wire(dispatch=mode):
            y, a = moe(p, x, rng=jax.random.PRNGKey(2), train=True)
        return jnp.sum(y ** 2) + a

    gd = jax.grad(lambda p: loss(p, "dense"))(params)
    gs = jax.grad(lambda p: loss(p, "sorted"))(params)
    for ld, ls in zip(jax.tree_util.tree_leaves(gd),
                      jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(ld), np.asarray(ls),
                                   rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# dropless mode
# ---------------------------------------------------------------------------

def test_dropless_serves_overflow_exactly_once():
    # every token prefers expert 0, capacity 2: the primary bucket
    # keeps 2, the overflow bucket (factor 1.0 = sized for everything)
    # serves the rest — output equals the loose-capacity oracle
    moe = _moe(E=2, k=1, factor=0.125, min_cap=2)
    params = moe.init(jax.random.PRNGKey(0))
    x = jnp.concatenate([jnp.ones((1, 16, 4)),
                         jnp.zeros((1, 16, 4))], axis=-1)
    x = x + 0.01 * jax.random.normal(jax.random.PRNGKey(1), x.shape)
    oracle_moe = _moe(E=2, k=1, factor=16.0, min_cap=16)
    with dsp.moe_wire(dispatch="sorted"):
        y_oracle, _ = oracle_moe(params, x, train=True)
    with dsp.moe_wire(dispatch="sorted", dropless=True,
                      overflow_factor=1.0):
        snap = COUNTERS.snapshot()
        y_dropless, _ = moe(params, x, train=True)
        jax.block_until_ready(y_dropless)
        d = _moe_deltas(snap)
    np.testing.assert_allclose(np.asarray(y_dropless),
                               np.asarray(y_oracle), rtol=1e-5,
                               atol=1e-6)
    assert d["moe.dropped_tokens"]["bytes"] == 0, d


def test_dropless_counts_overflow_past_the_bucket():
    # a bucket too small for the overflow still drops — and says so
    moe = _moe(E=2, k=1, factor=0.125, min_cap=2)
    params = moe.init(jax.random.PRNGKey(0))
    x = jnp.ones((1, 16, 8))
    with dsp.moe_wire(dispatch="sorted", dropless=True,
                      overflow_factor=0.25):  # 4 slots for 14 overflows
        snap = COUNTERS.snapshot()
        y, _ = moe(params, x, train=True)
        jax.block_until_ready(y)
        d = _moe_deltas(snap)
    assert d["moe.dropped_tokens"]["bytes"] == 16 - 2 - 4, d


def test_dropless_grads_flow_through_overflow():
    moe = _moe(E=2, k=1, factor=0.125, min_cap=1)
    params = moe.init(jax.random.PRNGKey(0))
    x = jnp.ones((1, 8, 8))

    def loss(p):
        with dsp.moe_wire(dispatch="sorted", dropless=True,
                          overflow_factor=1.0, counters=False):
            y, a = moe(p, x, train=True)
        return jnp.sum(y ** 2) + a

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["experts"]["w1"]).sum()) > 0
    assert float(jnp.abs(g["gate"]["w"]).sum()) > 0


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def test_sorted_dispatch_stats_pinned():
    # engineered routing: 8 tokens all on expert 0, capacity 2 -> 6
    # dropped, bucket utilisation = 2 used of E*C=4 slots = 50%
    moe = _moe(E=2, k=1, factor=0.25, min_cap=2, d=4, f=8)
    params = moe.init(jax.random.PRNGKey(0))
    params["gate"]["w"] = jnp.zeros((4, 2)).at[:, 0].set(5.0)
    x = jnp.ones((1, 8, 4))
    with dsp.moe_wire(dispatch="sorted"):
        snap = COUNTERS.snapshot()
        y, _ = moe(params, x, train=True)
        jax.block_until_ready(y)
        d = _moe_deltas(snap)
    assert d["moe.dropped_tokens"] == {"calls": 1, "bytes": 6}, d
    assert d["moe.capacity_frac"] == {"calls": 1, "bytes": 500000}, d


def test_counters_off_means_no_callbacks():
    moe = _moe(E=2, k=1, factor=2.0)
    params = moe.init(jax.random.PRNGKey(0))
    x = jnp.ones((1, 8, 8))
    with dsp.moe_wire(dispatch="sorted", counters=False):
        snap = COUNTERS.snapshot()
        jax.block_until_ready(moe(params, x, train=True)[0])
        assert _moe_deltas(snap) == {}


# ---------------------------------------------------------------------------
# the explicit a2a wire (8-device mesh)
# ---------------------------------------------------------------------------

def _wire_setup(E=8, k=2, S=12, B=8):
    moe = _moe(E=E, k=k, factor=2.0, min_cap=1)
    params = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, 8))
    return moe, params, x


@pytest.mark.parametrize("wire,tol", [("fp32", 5e-7), ("bf16", 2e-2),
                                      ("int8", 5e-2), ("int4", 0.5)])
def test_wire_parity_flat_mesh(wire, tol):
    make_mesh(data=8)
    moe, params, x = _wire_setup()
    y_d, aux_d = jax.jit(lambda p, x: moe(p, x, train=False))(params, x)
    with dsp.moe_wire(dispatch="sorted", a2a_wire_dtype=wire,
                      quant_block_size=16):
        y_w, aux_w = jax.jit(lambda p, x: moe(p, x, train=False))(params, x)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_w),
                               rtol=tol, atol=tol)
    assert abs(float(aux_d) - float(aux_w)) < 1e-6


def test_wire_bytes_pinned_to_plan_flat():
    info = make_mesh(data=8)
    moe, params, x = _wire_setup()
    cap = moe.capacity(12, train=False)
    with dsp.moe_wire(dispatch="sorted", a2a_wire_dtype="int8",
                      quant_block_size=16) as wcfg:
        plan = dsp.build_a2a_plan(wcfg, info, 8, 1, cap, 8)
        fwd = jax.jit(lambda p, x: moe(p, x, train=False)[0])
        snap = COUNTERS.snapshot()
        jax.block_until_ready(fwd(params, x))
        jax.block_until_ready(fwd(params, x))
        d = _moe_deltas(snap)
    # eval: 2 traversals (dispatch+combine) x 8 local shards x 2 calls
    assert d["moe.a2a_bytes"]["bytes"] == plan.bytes_per_traversal * 2 * 8 * 2
    assert d["moe.a2a_bytes"]["calls"] == plan.hops_per_traversal * 2 * 8 * 2
    assert "moe.a2a_inter" not in d  # flat mesh: no slow-fabric hop


def test_wire_bytes_pinned_to_plan_train_counts_backward():
    info = make_mesh(data=8)
    moe, params, x = _wire_setup()
    cap = moe.capacity(12, train=True)
    with dsp.moe_wire(dispatch="sorted", a2a_wire_dtype="bf16") as wcfg:
        plan = dsp.build_a2a_plan(wcfg, info, 8, 1, cap, 8)
        # differentiate wrt params AND x — as the engine does (x comes
        # from embedding params), so the dispatch-direction transpose
        # runs too
        step = jax.jit(jax.grad(
            lambda p, x: jnp.sum(moe(p, x, train=True)[0] ** 2),
            argnums=(0, 1)))
        snap = COUNTERS.snapshot()
        jax.block_until_ready(step(params, x))
        d = _moe_deltas(snap)
    # train: 4 traversals (fwd dispatch+combine + mirrored bwd)
    assert d["moe.a2a_bytes"]["bytes"] == plan.bytes_per_traversal * 4 * 8


def test_wire_inner_placement_keeps_exchange_on_fast_fabric():
    info = make_mesh(data=8, data_outer=2)
    moe, params, x = _wire_setup()
    y_ref, _ = jax.jit(lambda p, x: moe(p, x, train=False))(params, x)
    with dsp.moe_wire(dispatch="sorted", a2a_wire_dtype="fp32") as wcfg:
        assert dsp.resolve_placement(wcfg, info) == "inner"
        assert dsp.expert_axes(wcfg, info) == ("data_inner",)
        snap = COUNTERS.snapshot()
        y_w, _ = jax.jit(lambda p, x: moe(p, x, train=False))(params, x)
        jax.block_until_ready(y_w)
        d = _moe_deltas(snap)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_w),
                               rtol=2e-6, atol=2e-7)
    assert d["moe.a2a_bytes"]["bytes"] > 0
    assert "moe.a2a_inter" not in d, \
        "inner placement must keep the exchange off the slow fabric"


def test_wire_two_hop_split_pinned_per_level():
    info = make_mesh(data=8, data_outer=2)
    moe, params, x = _wire_setup()
    cap = moe.capacity(12, train=False)
    y_ref, _ = jax.jit(lambda p, x: moe(p, x, train=False))(params, x)
    with dsp.moe_wire(dispatch="sorted", placement="data",
                      a2a_wire_dtype_inner="fp32",
                      a2a_wire_dtype_outer="int8",
                      quant_block_size=16) as wcfg:
        assert dsp.resolve_placement(wcfg, info) == "data"
        plan = dsp.build_a2a_plan(wcfg, info, 8, 1, cap, 8)
        assert [h.wire for h in plan.hops] == ["fp32", "int8"]
        snap = COUNTERS.snapshot()
        y_w, _ = jax.jit(lambda p, x: moe(p, x, train=False))(params, x)
        jax.block_until_ready(y_w)
        d = _moe_deltas(snap)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_w),
                               rtol=5e-2, atol=5e-2)
    assert d["moe.a2a_bytes"]["bytes"] == plan.bytes_per_traversal * 2 * 8
    assert d["moe.a2a_inter"]["bytes"] == \
        plan.inter_bytes_per_traversal * 2 * 8
    # the quantized outer hop is smaller than the exact inner hop
    assert plan.inter_bytes_per_traversal < \
        plan.bytes_per_traversal - plan.inter_bytes_per_traversal


def test_wire_falls_back_on_indivisible_experts(caplog):
    make_mesh(data=8)
    moe, params, x = _wire_setup(E=6, k=1)  # 6 % 8 != 0
    with dsp.moe_wire(dispatch="sorted", a2a_wire_dtype="fp32"):
        dsp._warned.clear()
        snap = COUNTERS.snapshot()
        y, _ = jax.jit(lambda p, x: moe(p, x, train=False))(params, x)
        jax.block_until_ready(y)
        d = _moe_deltas(snap)
    assert "moe.a2a_bytes" not in d  # local dispatch, never silent:
    assert any("not divisible" in str(k) or "experts" in str(k)
               for k in dsp._warned)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def _cfg(moe):
    return {"train_batch_size": 8, "comm": {"moe": moe}}


def test_config_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown key.*typo_key"):
        dsp.parse_moe_config({"typo_key": 1})


def test_config_rejects_bad_dispatch():
    with pytest.raises(ValueError, match="dispatch.*dense.*sorted"):
        dsp.parse_moe_config({"dispatch": "hashed"})


def test_config_rejects_split_wire_naming_valid_set():
    with pytest.raises(ValueError, match=r"fp32.*bf16.*int8.*int4"):
        dsp.parse_moe_config({"a2a_wire_dtype": "split"})


def test_config_rejects_wire_on_dense_dispatch():
    with pytest.raises(ValueError, match="requires comm.moe.dispatch"):
        dsp.parse_moe_config({"dispatch": "dense",
                              "a2a_wire_dtype": "int8"})


def test_config_rejects_dropless_on_the_wire():
    with pytest.raises(ValueError, match="dropless.*overflow bucket"):
        dsp.parse_moe_config({"dropless": True, "a2a_wire_dtype": "int8"})


def test_config_rejects_placement_without_wire():
    with pytest.raises(ValueError, match="placement.*explicit"):
        dsp.parse_moe_config({"dispatch": "sorted", "placement": "inner"})


def test_config_rejects_odd_quant_block():
    with pytest.raises(ValueError, match="quant_block_size"):
        dsp.parse_moe_config({"a2a_wire_dtype": "int8",
                              "quant_block_size": 33})


def test_config_defaults():
    # absent block = the seed path; wire dtype alone implies sorted
    assert dsp.parse_moe_config(None) == dsp.MoEWireConfig()
    assert dsp.parse_moe_config({}).dispatch == "dense"
    c = dsp.parse_moe_config({"a2a_wire_dtype": "int8"})
    assert c.dispatch == "sorted" and c.explicit
    # per-level override alone implies the explicit wire, base exact
    c2 = dsp.parse_moe_config({"a2a_wire_dtype_outer": "int4"})
    assert c2.explicit and c2.wire_inner() == "fp32"
    assert c2.wire_outer() == "int4"


def test_config_overlap_knob_validated_and_falls_back(caplog):
    with pytest.raises(ValueError, match="overlap"):
        dsp.parse_moe_config({"a2a_wire_dtype": "int8",
                              "overlap": "soon"})
    cfg = dsp.parse_moe_config({"a2a_wire_dtype": "fp32",
                                "overlap": True})
    assert cfg.overlap == "on"
    # "on" engages the serial wire with a WARNING (never silent)
    make_mesh(data=8)
    moe, params, x = _wire_setup()
    with dsp.moe_wire(cfg):
        dsp._warned.clear()
        jax.block_until_ready(
            jax.jit(lambda p, x: moe(p, x, train=False)[0])(params, x))
    assert "overlap-on" in dsp._warned


def test_engine_rejects_bad_moe_config_at_init():
    with pytest.raises(Exception, match="a2a_wire_dtype"):
        deepspeed_tpu.DeepSpeedConfig(_cfg({"a2a_wire_dtype": "fp8"}))


# ---------------------------------------------------------------------------
# engine-level dryrun: loss parity with the dense path
# ---------------------------------------------------------------------------

def _engine_losses(comm, steps=3):
    from deepspeed_tpu.models import GPT, gpt2_config

    cfg = gpt2_config("nano", num_layers=2, num_experts=8, moe_top_k=2,
                      vocab_size=64, max_seq_len=16, dropout=0.0,
                      embed_dropout=0.0)
    c = {"train_batch_size": 8, "steps_per_print": 0,
         "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
         "mesh": {"data": 8}}
    if comm:
        c["comm"] = comm
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), config_params=c, dist_init_required=False)
    tok = np.random.RandomState(0).randint(0, 64, (8, 17)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])
    losses = []
    snap = COUNTERS.snapshot()
    for _ in range(steps):
        losses.append(float(engine.forward(batch)))
        engine.backward()
        engine.step()
    d = _moe_deltas(snap)
    return losses, d, engine


def test_engine_dryrun_sorted_tracks_dense():
    """Routing is identical between the engines; the losses differ at
    the one-ulp level from step 1 on, because XLA:CPU (jaxlib 0.9)
    contracts the dense combine einsum's multiply-add, while the sorted
    gather-and-sum is plain fp32 (pinned bit for bit against a numpy
    loop in test_sorted_combine_is_plain_fp32)."""
    dense, _, _ = _engine_losses(None)
    srt, d, _ = _engine_losses({"moe": {"dispatch": "sorted"}})
    for a, b in zip(dense, srt):
        assert abs(a - b) < 1e-5, (dense, srt)
    assert d["moe.dropped_tokens"]["calls"] > 0  # stats flowed


def test_sorted_combine_is_plain_fp32():
    """The sorted layer's output equals round(g1*o1) + round(g2*o2)
    computed in numpy, element for element: of the two engines it is
    the dense einsum, not this one, that fuses the multiply-add."""
    make_mesh(data=8)
    moe, params, x = _wire_setup()
    cap = moe.capacity(x.shape[1], train=True)
    with dsp.moe_wire(dispatch="sorted"):
        y = np.asarray(jax.jit(
            lambda p, x: moe(p, x, train=True)[0])(params, x))
    logits = jnp.einsum("bsd,de->bse", x, params["gate"]["w"])
    keys = jax.random.split(jax.random.PRNGKey(0), x.shape[0])
    eidx, gate, pos, keep, _ = jax.vmap(
        lambda lg, k: moe._route(lg, k, 0.0, cap))(logits, keys)
    expert_in = jax.vmap(lambda xr, er, pr, kr: dsp.sorted_dispatch_ref(
        xr, er, pr, kr, 8, cap))(x, eidx, pos, keep)
    out = np.asarray(moe._expert_ffn(
        expert_in.transpose(1, 0, 2, 3), params, x.dtype))  # [E,B,C,D]
    eidx, gate, pos, keep = map(np.asarray, (eidx, gate, pos, keep))
    ref = np.zeros_like(y)
    for b, k, s in zip(*np.nonzero(keep)):
        ref[b, s] += gate[b, k, s] * out[eidx[b, k, s], b, pos[b, k, s]]
    np.testing.assert_array_equal(y, ref)


def test_engine_dryrun_wire_pins_counters_and_loss():
    from deepspeed_tpu.models import gpt2_config

    dense, _, _ = _engine_losses(None)
    wired, d, engine = _engine_losses(
        {"moe": {"a2a_wire_dtype": "int8", "quant_block_size": 16}})
    for a, b in zip(dense, wired):
        assert abs(a - b) < 5e-2, (dense, wired)
    # plan pin: 2 MoE layers? nano nl=2 freq=2 -> layer 1 only; 4
    # traversals x 8 shards x layers x steps
    cap = MoE(gpt2_config("nano", num_layers=2, num_experts=8,
                          moe_top_k=2, vocab_size=64, max_seq_len=16
                          ).moe_config()).capacity(16, train=True)
    wcfg = dsp.parse_moe_config({"a2a_wire_dtype": "int8",
                                 "quant_block_size": 16})
    plan = dsp.build_a2a_plan(wcfg, engine.mesh_info, 8, 1, cap, 48)
    assert d["moe.a2a_bytes"]["bytes"] == \
        plan.bytes_per_traversal * 4 * 8 * 1 * 3, (d, plan.describe())


def test_engine_dryrun_hier_inner_placement():
    # data=8 factored outer=2 -> ep = data_inner = 4 ("data=ep=4"):
    # the moe wire waives the bucketed-only hierarchy gate, experts
    # place on data_inner, and the exchange never touches the slow hop
    dense, _, _ = _engine_losses(None)
    hier, d, engine = _engine_losses(
        {"hierarchy": {"outer": 2},
         "moe": {"a2a_wire_dtype": "fp32"}})
    assert engine.mesh_info.hierarchical
    w1 = engine.params["blocks"][1]["moe"]["experts"]["w1"]
    assert w1.sharding.spec[0] == "data_inner", w1.sharding.spec
    for a, b in zip(dense, hier):
        assert abs(a - b) < 1e-4, (dense, hier)
    assert d["moe.a2a_bytes"]["bytes"] > 0
    assert "moe.a2a_inter" not in d


@pytest.mark.slow
def test_bench_two_process_tcp(tmp_path):
    """The quantized expert-a2a wire over a REAL serialization boundary
    (2 jax.distributed processes, gloo/TCP): the bench's own byte-exact
    counter-vs-plan asserts run inside each worker, and the driver pins
    the bf16-vs-int8 compression ratio and cross-lane loss agreement
    from the printed lane table."""
    import json
    import os
    import subprocess
    import sys

    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "moe_a2a_bench.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, tool, "--nproc", "2", "--steps", "3",
         "--seq", "32", "--experts", "8", "--no-record"],
        capture_output=True, text=True, timeout=900,
        cwd=str(tmp_path), env=env)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("{") and "metric" in ln)
    r = json.loads(line)
    assert r["metric"] == "moe_a2a_2proc_tcp"
    # byte-exact plan pins already asserted in-process per lane; the
    # compression contract re-checked from the table
    bf16 = r["a2a_bf16"]["a2a_bytes_per_step"]
    int8 = r["a2a_int8"]["a2a_bytes_per_step"]
    assert bf16 / int8 >= 1.8, (bf16, int8)
    assert r["a2a_int8"]["counted_a2a_bytes"] == \
        r["a2a_int8"]["plan_a2a_bytes"]
    assert abs(r["dense"]["loss"] - r["sorted"]["loss"]) < 1e-4
    assert abs(r["dense"]["loss"] - r["a2a_fp32"]["loss"]) < 1e-3


def test_bench_dry_run(tmp_path):
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        bench = importlib.import_module("moe_a2a_bench")
    finally:
        sys.path.pop(0)
    result = bench.run_dry(str(tmp_path), steps=1, seq=16)
    assert result["a2a_int8"]["counted_a2a_bytes"] == \
        result["a2a_int8"]["plan_a2a_bytes"]
    assert result["value"] >= 1.8  # int8 bytes ~2x under bf16
    assert result["hier_inner_bf16"]["counted_inter_bytes"] == 0
    assert os.path.exists(os.path.join(
        str(tmp_path), os.path.basename(result["artifact"])))
