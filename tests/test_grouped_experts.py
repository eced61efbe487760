"""A prefill chunk's routed product over compact slabs of the rows this
chip holds (moe/dropless.py `experts_slabs`, PR 58), on the CPU: the
slab logic with XLA's grouped products and with the `grouped_experts`
kernel under the Pallas interpreter, each against `experts_masked`."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.kernels import moe_kernels
from deepspeed_tpu.kernels.registry import kernel_config, resolve_impl
from deepspeed_tpu.moe import dropless

D, F = 128, 256
IMPLS = ("jnp", "pallas")


def _experts(E, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return {"gate": jnp.asarray(rng.randn(E, D, F) * D ** -0.5, dtype),
            "up": jnp.asarray(rng.randn(E, D, F) * D ** -0.5, dtype),
            "down": jnp.asarray(rng.randn(E, F, D) * F ** -0.5, dtype)}


def _routing(T, k, total, seed=1):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    w, idx = dropless.route(x, jnp.asarray(rng.randn(D, total), jnp.float32),
                            k, renormalize=True)
    return x, w, idx


def _slabs(impl, x, ex, w, idx, total=None, held=None, live=None):
    with kernel_config(ops={"grouped_experts": impl}, interpret=True):
        return np.asarray(jax.jit(
            lambda x, ex, w, idx: dropless.experts_slabs(
                x, ex, w, idx, total, held, live))(x, ex, w, idx))


def _close(got, want, dtype=jnp.float32):
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, np.asarray(want), atol=tol, rtol=tol)


@pytest.fixture
def small_slabs(monkeypatch):
    """Slabs of whole 16 rows: several of them at a test's sizes."""
    monkeypatch.setattr(dropless, "SLAB_ROWS", 16)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("held_of,total", [(8, 64), (4, 64)],
                         ids=["an_eighth", "a_sixteenth"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_share_held_at_the_cells_ratios(impl, held_of, total, dtype,
                                          small_slabs):
    """1/8 (`longchat`, `mixedlen`) and 1/16 (`longctx`) of the experts
    held: a slab of twice the rows expected holds the chunk's, and the
    sum is the masked oracle's."""
    T, k = 96, 4
    ex = _experts(held_of, dtype)
    x, w, idx = _routing(T, k, total)
    w, idx, held = dropless.held_assignments(w, idx, 8, held_of)
    slabs, C = dropless.slabs_walked(idx, ex, total, held)
    assert C == dropless.slab_rows(T, k, held_of, total) < T * k
    assert int(slabs) == -(-int(held.sum()) // C)
    _close(_slabs(impl, x, ex, w, idx, total, held),
           dropless.experts_masked(x, ex, w, idx), dtype)


@pytest.mark.parametrize("impl", IMPLS)
def test_every_assignment_held_walks_several_slabs(impl, small_slabs):
    """The dropless guarantee under overflow: a chunk all of whose
    assignments fall on this chip's share holds T * top_k rows where a
    slab holds a quarter of them — four slabs, nothing dropped."""
    T, k, E, total = 64, 4, 8, 64
    ex = _experts(E)
    x, w, idx = _routing(T, k, total)
    w, idx, held = dropless.held_assignments(w, idx % E, 0, E)
    assert bool(held.all())
    slabs, C = dropless.slabs_walked(idx, ex, total, held)
    assert (int(slabs), C) == (4, 64)
    _close(_slabs(impl, x, ex, w, idx, total, held),
           dropless.experts_masked(x, ex, w, idx))


@pytest.mark.parametrize("impl", IMPLS)
def test_no_assignment_held_walks_no_slab(impl, small_slabs):
    T, k, E, total = 64, 4, 4, 32
    ex = _experts(E)
    x, w, idx = _routing(T, k, total)
    w, idx, held = dropless.held_assignments(w, idx % 8 + 16, 0, E)
    assert not bool(held.any())
    slabs, _ = dropless.slabs_walked(idx, ex, total, held)
    assert int(slabs) == 0
    got = _slabs(impl, x, ex, w, idx, total, held)
    assert np.array_equal(got, np.zeros_like(got))


@pytest.mark.parametrize("impl", IMPLS)
def test_an_empty_expert_and_one_across_two_windows_and_two_slabs(
        impl, small_slabs, monkeypatch):
    """Expert 1 has no row; expert 2's rows start in one window of the
    kernel's walk and end in the next, and lie across the first slab's
    end; experts 0 and 3 take the rest."""
    monkeypatch.setattr(moe_kernels, "GROUPED_WINDOW", 16)
    T, k, E, total = 48, 2, 4, 16
    ex = _experts(E)
    x, w, _ = _routing(T, k, total)
    # rows sorted: 10 of expert 0, none of 1, 44 of 2 (rows 10..53, over
    # four windows and the slab's end at 48), 6 of 3, 36 elsewhere
    flat = np.array([0] * 10 + [2] * 44 + [3] * 6 + [9] * 36)
    idx = jnp.asarray(np.random.RandomState(3).permutation(flat)
                      .reshape(T, k), jnp.int32)
    w, idx, held = dropless.held_assignments(w, idx, 0, E)
    slabs, C = dropless.slabs_walked(idx, ex, total, held)
    assert (int(slabs), C) == (2, 48)
    _close(_slabs(impl, x, ex, w, idx, total, held),
           dropless.experts_masked(x, ex, w, idx))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_every_expert_held_is_one_slab_of_every_row(impl, dtype):
    """`chatgen`'s case: no share, `held` None — one slab, statically,
    of all T * top_k rows, and no loop in the program."""
    T, k, E = 40, 6, 16
    ex = _experts(E, dtype)
    x, w, idx = _routing(T, k, E)
    assert dropless.slabs_walked(idx, ex) == (1, T * k)
    with kernel_config(ops={"grouped_experts": impl}, interpret=True):
        text = str(jax.make_jaxpr(dropless.experts_slabs)(x, ex, w, idx))
    assert "while" not in text.split("pallas_call")[0]
    _close(_slabs(impl, x, ex, w, idx),
           dropless.experts_masked(x, ex, w, idx), dtype)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("share", [True, False], ids=["share", "all"])
def test_rows_of_dead_assignments_never_reach_the_sum(impl, share,
                                                      small_slabs):
    """Tokens that are not live hold NaN: their assignments sort behind
    every group, and neither the products nor the sum back to token
    order let a live token see them."""
    T, k, total = 64, 4, 32
    E = 8 if share else total
    ex = _experts(E)
    x, w, idx = _routing(T, k, total)
    held = None
    if share:
        w, idx, held = dropless.held_assignments(w, idx, 4, E)
    live = jnp.arange(T) < 40
    want = np.asarray(dropless.experts_masked(x, ex, w, idx))[:40]
    got = _slabs(impl, jnp.where(live[:, None], x, jnp.nan), ex, w, idx,
                 total, held, live)
    _close(got[:40], want)
    assert np.array_equal(got[40:], np.zeros_like(got[40:]))


def test_over_the_ridge_the_chip_walks_slabs_and_counts_their_rows(
        chip_rule, small_slabs):
    """`routed_experts` as a prefill chunk calls it: on the chip (its
    shape rule deciding, the kernel interpreted) a call over the ridge
    takes the slabs and `rows_multiplied` says slabs x a slab's rows —
    with a padded tail that is not live fewer; off it the grouped
    products over every assignment, and T x top_k."""
    T, k, E, total = 160, 4, 8, 64
    ex = _experts(E)
    x, w, idx = _routing(T, k, total)
    w, idx, held = dropless.held_assignments(w, idx, 16, E)
    live = jnp.arange(T) < 50
    want = dropless.experts_masked(x, ex, w, idx)
    with chip_rule("grouped_experts"):
        assert dropless.routed_way(T, k, ex, total) == "slabs"
        assert dropless.routed_way(128, k, ex, total) == "masked"
        got = jax.jit(lambda *a: dropless.routed_experts(
            *a, total=total, held=held))(x, ex, w, idx)
        rows = int(dropless.rows_multiplied(idx, ex, total, held))
        tail = int(dropless.rows_multiplied(idx, ex, total, held, live))
    C = dropless.slab_rows(T, k, E, total)
    assert C == 160 and rows == -(-int(held.sum()) // C) * C
    assert tail == -(-int(held[:50].sum()) // C) * C <= rows
    _close(got, want)
    assert dropless.routed_way(T, k, ex, total) == "grouped"
    assert int(dropless.rows_multiplied(idx, ex, total, held)) == T * k


_INFO = dict(tokens=512, rows=1280, num_experts=64, model_dim=2048,
             expert_dim=512, itemsize=2)


@pytest.mark.parametrize("change,why", [
    ({}, None),                                    # longchat's chunk
    (dict(rows=1024, num_experts=16, model_dim=4096, expert_dim=4096), None),
    (dict(rows=512, num_experts=16, model_dim=6144, expert_dim=2048), None),
    (dict(rows=3072, expert_dim=1408), None),      # chatgen's: every row
    (dict(tokens=129), None),
    (dict(tokens=128), "128 rows are under the ridge"),
    (dict(tokens=32), "32 rows are under the ridge"),
    (dict(model_dim=1000), "rows of 1000 values are not whole 128-lane"),
    (dict(rows=16384, model_dim=8192, expert_dim=2048),
     "do not fit the kernel's VMEM"),
], ids=lambda v: "-".join(f"{k}{x}" for k, x in v.items())
    if isinstance(v, dict) else "")
def test_grouped_experts_shape_rule(change, why, native):
    """What the call can see decides (moe/dropless.py::grouped_info): on
    the chip a prefill chunk of each routed cell takes the kernel; a
    call under the ridge, rows the kernel cannot tile and a slab that
    does not fit VMEM beside a tile of the weights do not, and say why
    when it is forced."""
    info = dict(_INFO, **change)
    if why is None:
        assert resolve_impl("grouped_experts", info=info) == "pallas"
        return
    assert resolve_impl("grouped_experts", info=info) == "jnp"
    with pytest.raises(RuntimeError, match=why):
        resolve_impl("grouped_experts", impl="pallas", info=info)


@pytest.mark.parametrize("tokens,top_k,count,total,rows", [
    (512, 10, 64, 512, 1280),      # longchat
    (512, 8, 16, 128, 1024),       # mixedlen
    (512, 8, 16, 256, 512),        # longctx
    (512, 6, 64, 64, 3072),        # chatgen: every row
    (8, 4, 4, 8, 32),              # never more rows than there are
])
def test_slab_rows_at_the_cells_shapes(tokens, top_k, count, total, rows):
    assert dropless.slab_rows(tokens, top_k, count, total) == rows


def test_the_kernels_tiles_and_vmem_at_the_cells_shapes():
    """An expert's columns as one tile where slab, result and the three
    matrices fit (`longchat`, `chatgen`), tiles of 1,024 and 512 where
    they do not; the VMEM asked is what they need, under the chip's."""
    for rows, d, f, tile in ((1280, 2048, 512, 512), (3072, 2048, 1408, 1408),
                             (1024, 4096, 4096, 1024),
                             (512, 6144, 2048, 512)):
        assert moe_kernels.grouped_tile(rows, d, f, 2) == tile
        assert moe_kernels.grouped_vmem(rows, d, tile, 2) <= 100 << 20
    assert moe_kernels.grouped_tile(16384, 8192, 2048, 2) == 0
