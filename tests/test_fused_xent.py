"""Fused streaming cross-entropy kernel: value + gradient parity against
the XLA formulation (interpret mode; same kernels compile for TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import GPT, gpt2_config
from deepspeed_tpu.ops.transformer.fused_xent import fused_softmax_xent_sum

N, D, V = 512, 64, 1024
BR, BV = 256, 512


def _inputs(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (N, D), jnp.float32) * 0.5
    w = jax.random.normal(ks[1], (D, V), jnp.float32) * 0.1
    labels = jax.random.randint(ks[2], (N,), 0, V)
    valid = jnp.arange(N) % 5 != 0  # exercise masking
    return x, w, labels, valid


def _ref(x, w, labels, valid):
    logits = (x @ w).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return jnp.sum(jnp.where(valid, lse - ll, 0.0))


def test_fused_xent_forward_parity():
    x, w, labels, valid = _inputs()
    got = fused_softmax_xent_sum(x, w, labels, valid, BR, BV)
    want = _ref(x, w, labels, valid)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_fused_xent_gradient_parity():
    x, w, labels, valid = _inputs(1)

    g1 = jax.grad(lambda a, b: fused_softmax_xent_sum(
        a, b, labels, valid, BR, BV) / 37.0, argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda a, b: _ref(a, b, labels, valid) / 37.0,
                  argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-6)


@pytest.mark.slow
def test_pallas_loss_impl_through_gpt():
    """loss_impl='pallas' must give the same loss/grads as the XLA path
    through the full model (vocab 50304-style multiple-of-512 shapes)."""
    cfg_kw = dict(vocab_size=1024, max_seq_len=64, num_layers=2,
                  num_heads=2, d_model=64, shard_activations=False)
    tok = jax.random.randint(jax.random.PRNGKey(2), (4, 65), 0, 1024)
    batch = (tok[:, :-1], tok[:, 1:])

    m_x = GPT(gpt2_config("nano", **cfg_kw))
    params = m_x.init(jax.random.PRNGKey(0))
    l_xla, g_xla = jax.value_and_grad(m_x.loss)(params, batch)

    m_p = GPT(gpt2_config("nano", loss_impl="pallas", **cfg_kw))
    l_pal, g_pal = jax.value_and_grad(m_p.loss)(params, batch)

    np.testing.assert_allclose(float(l_pal), float(l_xla), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=2e-5),
        g_pal, g_xla)


def test_dispatch_engages_for_gpt2_real_vocab(monkeypatch):
    """vocab 50304 (the padded GPT-2 family size) must reach the kernel
    (block_v 384 divides it) — a silent XLA fallback would report kernel
    perf numbers for the wrong code path."""
    from deepspeed_tpu.models import gpt as gpt_mod

    calls = []

    def fake(x, w, labels, valid, br, bv):
        calls.append((int(x.shape[0]), int(w.shape[1]), br, bv))
        return jnp.zeros((), jnp.float32)

    monkeypatch.setattr(
        "deepspeed_tpu.ops.transformer.fused_xent.fused_softmax_xent_sum",
        fake)
    x = jnp.zeros((512, 32))
    w = jnp.zeros((32, 50304))
    labels = jnp.zeros((512,), jnp.int32)
    valid = jnp.ones((512,), bool)
    gpt_mod._softmax_xent_from_hidden(x, w, labels, valid, impl="pallas")
    assert calls == [(512, 50304, 256, 384)], calls


def test_dispatch_rejects_tp_mesh():
    from deepspeed_tpu import comm
    from deepspeed_tpu.models import gpt as gpt_mod

    comm.make_mesh(data=4, model=2)
    x = jnp.zeros((512, 32))
    w = jnp.zeros((32, 1024))
    labels = jnp.zeros((512,), jnp.int32)
    valid = jnp.ones((512,), bool)
    with pytest.raises(ValueError, match="vocab-parallel"):
        gpt_mod._softmax_xent_from_hidden(x, w, labels, valid,
                                          impl="pallas")


def test_blocks_that_do_not_divide_are_refused():
    """GPT-2's padded vocab 50304 = 393 * 128 is not a multiple of the
    default block of 512: the grid would drop the last 128 columns."""
    from deepspeed_tpu.ops.transformer.fused_xent import pick_blocks

    assert pick_blocks(4096, 50304) == (256, 384)
    x, w, labels, valid = _inputs()
    with pytest.raises(ValueError, match="do not divide"):
        fused_softmax_xent_sum(x, w[:, :-128], labels, valid, BR, BV)
