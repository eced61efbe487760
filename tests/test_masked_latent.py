"""A prefill chunk's attention over a learned selection: the kernel
(kernels/masked_latent.py, under the Pallas interpreter) against its
oracle, `serving/sparse.py::attend_tiles` — the walk's edges at toy
widths, and two chunks of one request through the engine.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.kernels import masked_latent, registry
from deepspeed_tpu.models.glm_moe_dsa import select_mask
from deepspeed_tpu.monitor.counters import COUNTERS
from deepspeed_tpu.serving import sparse

T, H, NOPE, ROPE, V, RANK = 32, 4, 24, 8, 16, 32
BS, WIDTH, TILE, TOPK = 4, 16, 16, 12          # a table of 4 tiles
CFG = types.SimpleNamespace(kv_lora_rank=RANK, v_head_dim=V,
                            qk_rope_head_dim=ROPE, head_dim=NOPE + ROPE,
                            yarn=None)
SCHED = types.SimpleNamespace(block_size=BS)


def _operands(dtype, n_tiles, seed=0):
    """One request whose table holds `n_tiles` tiles of its own rows and
    NaN in every block behind them, in the pool and through the table."""
    rng = np.random.RandomState(seed)
    blocks = WIDTH + 5
    pool = rng.randn(blocks, BS, RANK + ROPE + 8).astype(np.float32)
    table = (rng.permutation(blocks - 1)[:WIDTH] + 1).astype(np.int32)
    live = np.zeros(blocks, bool)
    live[table[:n_tiles * TILE // BS]] = True
    pool[~live] = np.nan
    kv_b = rng.randn(RANK, H * (NOPE + V)).astype(np.float32) * RANK ** -.5
    q_nope = rng.randn(1, T, H, NOPE).astype(np.float32)
    q_rope = rng.randn(1, T, H, ROPE).astype(np.float32)
    cast = lambda a: jnp.asarray(a, dtype)
    return (cast(kv_b), cast(q_nope), cast(q_rope),
            cast(pool.reshape(blocks * BS, -1)), jnp.asarray(table)[None])


def _chosen(q_pos, seed=1):
    """`dsa_select`'s mask for queries at `q_pos` [T] over random scores:
    the `TOPK` largest a query sees, all it sees while it has no more."""
    scores = jnp.asarray(np.random.RandomState(seed).randn(
        1, T, WIDTH * BS).astype(np.float32))
    seen = jnp.arange(WIDTH * BS)[None, None, :] <= \
        jnp.asarray(q_pos)[None, :, None]
    return select_mask(scores, seen, TOPK)


def _case(name):
    """(n_tiles, mask [1, T, L]) of a named edge of the walk."""
    second = TILE + np.arange(T)              # a request's second chunk
    if name == "tiles_behind_are_not_read":
        return 2, _chosen(np.minimum(second, 2 * TILE - 1))
    if name == "fewer_visible_rows_than_topk":
        mask = _chosen(np.arange(T))          # the first chunk: 1..T rows
        assert int(mask[0, 3].sum()) == 4 and int(mask[0, -1].sum()) == TOPK
        return 2, mask
    if name == "a_tile_some_query_chose_nothing_from":
        mask = np.array(_chosen(second + TILE))
        mask[0, 5, TILE:2 * TILE] = False
        mask[0, 9, :TILE] = False             # the query's first tile
        return 3, jnp.asarray(mask)
    if name == "a_slot_that_is_not_running":
        return 1, _chosen(np.full(T, -1))
    if name == "the_whole_table":
        return WIDTH * BS // TILE, _chosen(3 * TILE + np.arange(T) // 2)
    raise KeyError(name)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", [
    "tiles_behind_are_not_read", "fewer_visible_rows_than_topk",
    "a_tile_some_query_chose_nothing_from", "a_slot_that_is_not_running",
    "the_whole_table"])
def test_kernel_matches_attend_tiles(name, dtype):
    """Same roundings, same mask, same tiles walked: only the order of
    float32 sums may differ, so the kernel's result is the oracle's to
    float32 rounding whatever the rows' dtype — and NaN-free, which it
    could not be had it read a row behind the tiles walked."""
    n_tiles, mask = _case(name)
    args = (CFG, *_operands(dtype, n_tiles), mask, jnp.int32(n_tiles), SCHED,
            TILE)
    want = np.asarray(sparse.attend_tiles(*args))
    with registry.kernel_config(ops={"masked_latent_attention": "pallas"},
                                interpret=True):
        got = np.asarray(registry.dispatch("masked_latent_attention", *args))
    assert got.shape == want.shape == (1, T, H * V)
    assert np.isfinite(want).all() and np.isfinite(got).all()
    assert np.abs(got - want).max() < 2e-6 * max(1.0, np.abs(want).max())
    if name == "a_slot_that_is_not_running":
        assert not mask.any() and not got.any()
    else:
        assert mask.any(-1).all() and np.abs(got).max() > 0.1


def test_walked_rows_reads_the_tiles_walked_alone():
    """The rows the call lays out for the kernel: `n_tiles` tiles of the
    table as `[c | zeros | rotated key]`, gathered a tile at a time."""
    _, _, _, pool, tables = _operands(jnp.float32, 2)
    rows = masked_latent.walked_rows(
        pool, tables, jnp.int32(2), tile=TILE, block_size=BS, rank=RANK,
        nope=NOPE, rope=ROPE)
    want = sparse._tile_rows(pool, tables, 0, 2 * TILE // BS, BS,
                             RANK + ROPE)[0]
    got = np.asarray(rows[:2 * TILE])
    assert rows.shape == (WIDTH * BS, RANK + NOPE + ROPE)
    assert np.array_equal(got[:, :RANK], want[:, :RANK])
    assert not got[:, RANK:RANK + NOPE].any()
    assert np.array_equal(got[:, RANK + NOPE:], want[:, RANK:])


@pytest.mark.parametrize("info,why", [
    (dict(batch=2), "2 sequences"),
    (dict(kv_itemsize=4, w_itemsize=4), "bytes a value"),
    (dict(q_len=40), "whole 128-lane"),
    (dict(nope=100), "whole 128-lane"),
    (dict(tile=8192, q_len=4096), "MiB of VMEM"),
])
def test_shape_rule_refuses_by_sentence(info, why):
    cell = dict(batch=1, q_len=512, tile=1024, rank=512, nope=192, rope=64,
                v=256, kv_itemsize=2, w_itemsize=2)
    op = registry.get_kernel("masked_latent_attention")
    assert op.auto_supports("default", cell) == (True, "")
    ok, said = op.auto_supports("default", dict(cell, **info))
    assert not ok and why in said


def test_two_chunks_of_a_request_in_a_row(monkeypatch):
    """A prompt of 29 through chunks of 8 and tiles of 16 — four chunks,
    the last three under a true selection, two tiles from the second on —
    and six decoded tokens: with the kernel in every layer's chunk the
    engine draws from the logits the oracle's engine draws from."""
    from test_evabyte import Probe
    from test_glm_moe_dsa import _model, _prompt, _serve

    monkeypatch.setattr(sparse, "KEY_TILE", 16)
    model, params = _model()
    logits, outs = [], []
    for impl in ("jnp", "pallas"):
        snap = COUNTERS.snapshot()
        with registry.kernel_config(ops={"masked_latent_attention": impl},
                                    interpret=True):
            probe = Probe(model, params, _serve(max_batch=1))
            req = probe.engine.submit(_prompt(29, 5), 6)
            probe.run()
        assert req.state == "finished"
        # `prefill` is traced once: its five layers' calls, and no other
        took = COUNTERS.delta_since(snap).get(
            "kernel.dispatches" if impl == "pallas" else "kernel.fallbacks")
        assert took["calls"] == 5
        logits.append(np.stack(probe.logits[req.rid])[:len(req.out)])
        outs.append(req.out)
    assert outs[0] == outs[1]
    assert np.abs(logits[0] - logits[1]).max() < 1e-4
