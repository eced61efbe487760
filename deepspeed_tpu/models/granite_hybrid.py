"""Granite 4.0-H (`model_type` `granitemoehybrid`, dense): Mamba-2
state-space layers beside a few grouped-attention layers, no positions
anywhere, four scalars on the stream.

Embedding times `embedding_multiplier`; every layer pre-norm (RMSNorm,
the gain w) and sequential, both branches times `residual_multiplier`:
x <- x + r mixer(norm x), x <- x + r MLP(norm x), the MLP a SiLU-gated
FFN; final RMSNorm, tied head, logits over `logits_scaling`.  The
residual stream is float32.

Which mixer a layer has follows `layer_types` — a pattern of `period`
layers with attention at `attention_at`:

Attention: `num_heads` query heads on `kv_heads` keys and values of
`head_dim`, query head n reading K/V head n // (num_heads / kv_heads);
no bias, NO rotation, scores times `attention_multiplier` (not
head_dim ** -0.5), causal softmax.  A token's cache row in such a layer
is its `kv_heads` keys and values.

Mamba-2 (one group; `d_in` = `ssm_heads` x `ssm_head_dim`, the
convolution's `conv_width` = d_in + 2 `ssm_state` channels), for the
token at t with u_t the normed stream:
  [z_t | xBC_t | dt_t] = u_t W_in                  (d_in | conv_width | heads)
  c_t = silu(b_c + sum_j w_c[:, j] xBC_{t - (K-1) + j})   (K taps, causal,
                                                    zeros before the start)
  [x_t | B_t | C_t] = c_t                          (d_in | state | state)
  D_t = softplus(dt_t + dt_bias),  a_t = exp(D_t A),  A = -exp(A_log)
  H_t = a_t H_{t-1} + D_t x_t (x) B_t   per head [head_dim, state], H_{-1} = 0
  y_t = H_t C_t + D x_t
  out_t = W_out (w_n (y_t silu(z_t)) / rms(y_t silu(z_t)))   over all d_in
Decays, D_t and the state are float32.  Such a layer keeps, for a
request, H and the convolution's last K - 1 inputs and nothing else:
`ssm_mix` takes both and hands both back, moved on by the call's valid
positions — by the recurrence itself where the call is one token
(`ssm_step`: decode; on a TPU the kernel of kernels/ssm.py, which steps
the sequences with a valid position and touches no other's state), by
the chunked form of it where it is many
(`ssm_scan`: a prefill chunk, `ssm_chunk` positions at a time — inside a
chunk the scores C_t . B_s under the cumulative decay, plus the incoming
state decayed to each position; the outgoing state the incoming one
decayed plus the chunk's decayed inputs).  A position past the call's
valid ones has D_t = 0 — the state passes it unchanged — and is not among
the convolution's inputs kept.

The serving engine runs the model through `layer_spec()`
(`serving/layers.py` holds the cached block, built from the pieces
here); `apply` is the uncached forward the tests compare with the plain
reference (`benchmarks/reference/granite_hybrid.py`, which knows the
recurrence only).  Training it, routed experts (`num_local_experts` > 0)
and a mesh are not built; more than one group of B and C is
(`spec.ssm_groups`: `by_group`, `gated_norm`; models/nemotron_h.py has
eight).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from .cohere2_moe import attend_grouped, project_grouped
from .deepseek_v2 import rms_norm_plain
from .evabyte import matmul32, silu_gated_ffn
from .layer_spec import LayerSpec


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 131072
    num_layers: int = 40
    period: int = 10                 # layers of one turn of the pattern
    attention_at: tuple = (5,)       # which of a period attend
    d_model: int = 2048
    d_ffn: int = 8192
    num_heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    init_std: float = 0.02           # seeded weights: every matrix
    init_a: tuple = (1.0, 16.0)      # -A uniform in it
    init_dt: tuple = (0.001, 0.1)    # softplus(dt_bias) log-uniform in it
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(f"num_heads {self.num_heads} must be a "
                             f"multiple of kv_heads ({self.kv_heads})")
        self.attention_at = tuple(int(i) for i in self.attention_at)
        if self.period < 1 or any(not 0 <= i < self.period
                                  for i in self.attention_at):
            raise ValueError(
                f"attention_at {self.attention_at} names layers of a "
                f"period of {self.period}")
        if self.ssm_conv < 2:
            raise ValueError("the convolution has at least 2 taps")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    def attends(self, layer: int) -> bool:
        return layer % self.period in self.attention_at


# ---------------------------------------------------------------------------
# the state-space mixer (shared with serving/layers.py)
# ---------------------------------------------------------------------------


def ssm_step(x, Bm, Cm, dt, A, state):
    """The recurrence, one token a sequence: x [B, H, P], Bm, Cm [B, N],
    dt [B, H] (0: the state passes unchanged), A [H], state
    [B, H, P, N], all float32 -> (y [B, H, P], state).  No term crosses
    sequences."""
    a = jnp.exp(dt * A)
    state = state * a[:, :, None, None] + \
        (dt[:, :, None] * x)[..., None] * Bm[:, None, None, :]
    return jnp.sum(state * Cm[:, None, None, :], axis=-1), state


def ssm_scan(x, Bm, Cm, dt, A, state, chunk: int):
    """The same recurrence over T positions, `chunk` at a time: x
    [B, T, H, P], Bm, Cm [B, T, N], dt [B, T, H], state [B, H, P, N]
    -> (y [B, T, H, P], state after the last position).  T is a multiple
    of `chunk`."""
    B, T, H, P = x.shape
    n = T // chunk
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def chunks(t):                      # [B, T, ...] -> [n, B, chunk, ...]
        return jnp.moveaxis(t.reshape((B, n, chunk) + t.shape[2:]), 1, 0)

    def one(state, inp):
        x, Bm, Cm, dt = inp
        cum = jnp.cumsum(dt * A, axis=1)                     # [B, Q, H]
        # position s's input as position t sees it: decayed by
        # exp(cum_t - cum_s), s <= t
        decay = jnp.exp(jnp.where(
            causal[None, :, :, None],
            cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
        scores = jnp.einsum("btn,bsn->bts", Cm, Bm)
        y = jnp.einsum("btsh,bshp->bthp",
                       scores[..., None] * decay * dt[:, None, :, :], x)
        y += jnp.einsum("bhpn,btn->bthp", state, Cm) * \
            jnp.exp(cum)[..., None]
        tail = jnp.exp(cum[:, -1:, :] - cum) * dt            # [B, Q, H]
        state = state * jnp.exp(cum[:, -1, :])[:, :, None, None] + \
            jnp.einsum("bshp,bsn->bhpn", x * tail[..., None], Bm)
        return state, y

    state, y = jax.lax.scan(one, state,
                            (chunks(x), chunks(Bm), chunks(Cm), chunks(dt)))
    return jnp.moveaxis(y, 0, 1).reshape(B, T, H, P), state


def by_group(fn, groups: int):
    """`ssm_step` or `ssm_scan` where the heads lie in `groups` groups,
    each with a B and a C of its own (Bm, Cm [..., groups, N], head h
    reading group h // (H / groups)): the one-group function mapped
    over the groups, each a recurrence over its own heads — same
    arguments and results."""

    def split(t, axis):            # the heads' axis -> (groups, heads of one)
        return t.reshape(t.shape[:axis] + (groups, -1) + t.shape[axis + 1:])

    def grouped(x, Bm, Cm, dt, A, state, *rest):
        a = x.ndim - 2             # the heads' axis of x (and of dt)
        y, new = jax.vmap(lambda *g: fn(*g, *rest),
                          in_axes=(a, a, a, a, 0, 1), out_axes=(a, 1))(
            split(x, a), Bm, Cm, split(dt, a), split(A, 0), split(state, 1))
        return y.reshape(x.shape), new.reshape(state.shape)

    return grouped


def gated_norm(spec, g, p):
    """The mixer's RMS norm of the gated values g [..., d_in]: over all
    of them, or, with `spec.ssm_groups` > 1, over each group's apart
    (one gain of d_in either way)."""
    G = spec.ssm_groups
    if G == 1:
        return rms_norm_plain(g, p, spec.eps)
    return rms_norm_plain(g.reshape(g.shape[:-1] + (G, -1)),
                          {"scale": p["scale"].reshape(G, -1)},
                          spec.eps).reshape(g.shape)


def ssm_mix(spec, p, h, state, conv, n_valid, live=None):
    """The Mamba-2 mixer over h [B, T, D] (normed) from a request's
    `state` [B, H, P, N] float32 and the convolution's last inputs
    `conv` [B, K - 1, conv_width]; `n_valid` [B]: how many of the T
    positions are real.  -> (out [B, T, D] float32, state, conv), both
    moved on by the valid positions and by nothing else.  Where T is 1
    the recurrence goes through the kernel registry's `ssm_step`, which
    may walk `live` — `kernels/ssm.py::live_slots(n_valid)`, worked out
    here unless the caller has it for all its layers — and leave every
    other sequence's state where it lies."""
    B, T, _ = h.shape
    H, P, N = spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state
    K, d_in, G = spec.ssm_conv, spec.ssm_heads * spec.ssm_head_dim, \
        spec.ssm_groups
    z, xBC, dt = jnp.split(matmul32(h, p["in"]),
                           [d_in, d_in + spec.ssm_conv_width], axis=-1)
    # the convolution's inputs at the dtype they are kept in between
    # calls: where a call ends must not show
    seq = jnp.concatenate(
        [conv, xBC.astype(conv.dtype)], axis=1).astype(jnp.float32)
    w = p["conv_w"].astype(jnp.float32)                       # [conv, K]
    c = jax.nn.silu(p["conv_b"].astype(jnp.float32) + sum(
        seq[:, j:j + T] * w[:, j] for j in range(K)))
    # the last K - 1 VALID inputs: rows n_valid .. of [kept | call]
    conv = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
        s, n, K - 1, axis=0))(seq, n_valid).astype(conv.dtype)
    x, Bm, Cm = jnp.split(c, [d_in, d_in + G * N], axis=-1)
    x = x.reshape(B, T, H, P)
    if G > 1:                      # a B and a C a group: [B, T, G, N]
        Bm, Cm = Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
    valid = jnp.arange(T)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], jax.nn.softplus(
        dt + p["dt_bias"].astype(jnp.float32)), 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if T == 1:
        from ..kernels import registry
        from ..kernels.ssm import live_slots, ssm_step_info

        y, state = registry.dispatch(
            "ssm_step", x[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], A, state,
            *(live_slots(n_valid) if live is None else live),
            info=ssm_step_info(state, G))
        y = y[:, None]
    else:
        scan = ssm_scan if G == 1 else by_group(ssm_scan, G)
        y, state = scan(x, Bm, Cm, dt, A, state, min(spec.ssm_chunk, T))
    y = y + p["D"].astype(jnp.float32)[:, None] * x
    g = gated_norm(spec, y.reshape(B, T, d_in) * jax.nn.silu(z), p["norm"])
    return matmul32(g, p["out"]), state, conv


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class GraniteHybrid:
    """Granite 4.0-H's language model as the serving engine and the
    tests take it: `init` from a key, `apply` (uncached), `layer_spec`
    (what serving builds its programs from)."""

    def __init__(self, config: GraniteHybridConfig):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        return LayerSpec(
            norm="rmsnorm", positions="none", attention="grouped",
            ffn="silu_gated", head="tied", eps=c.rms_norm_eps,
            kv_heads=c.kv_heads,
            layer_mixers=tuple("attention" if c.attends(i) else "ssm"
                               for i in range(c.period)),
            ssm_heads=c.ssm_heads, ssm_head_dim=c.ssm_head_dim,
            ssm_state=c.ssm_state, ssm_conv=c.ssm_conv,
            ssm_chunk=c.ssm_chunk, embed_scale=c.embedding_multiplier,
            residual_scale=c.residual_multiplier,
            attn_scale=c.attention_multiplier,
            logit_divisor=c.logits_scaling).validate()

    def init(self, rng):
        c = self.config
        d, dt, std = c.d_model, c.param_dtype, c.init_std
        H, KV, dh = c.num_heads, c.kv_heads, c.head_dim

        def normal(key, shape):
            return (jax.random.normal(key, shape) * std).astype(dt)

        def uniform(key, lo, hi):
            return jax.random.uniform(key, (c.ssm_heads,), jnp.float32,
                                      lo, hi)

        def mixer(key):
            k = jax.random.split(key, 5)
            # Mamba-2's own ranges: a head forgets over 1/(D A) tokens,
            # from a handful to thousands
            step = jnp.exp(uniform(k[3], *map(math.log, c.init_dt)))
            return {"in": normal(k[0], (d, c.d_inner + c.conv_width
                                        + c.ssm_heads)),
                    # the taps as Mamba-2 draws them (a depthwise
                    # Conv1d's default): at the matrices' 0.02 x, B and C
                    # are ~0.02, the recurrence adds a thousandth of what
                    # the skip D x does, and no check sees the state
                    "conv_w": jax.random.uniform(
                        k[1], (c.conv_width, c.ssm_conv), jnp.float32,
                        -c.ssm_conv ** -0.5, c.ssm_conv ** -0.5).astype(dt),
                    "conv_b": jnp.zeros((c.conv_width,), dt),
                    "A_log": jnp.log(uniform(k[2], *c.init_a)),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "D": jnp.ones((c.ssm_heads,), jnp.float32),
                    "norm": {"scale": jnp.ones((c.d_inner,), dt)},
                    "out": normal(k[4], (c.d_inner, d))}

        def attention(key):
            k = jax.random.split(key, 4)
            return {"q": normal(k[0], (d, H * dh)),
                    "k": normal(k[1], (d, KV * dh)),
                    "v": normal(k[2], (d, KV * dh)),
                    "o": normal(k[3], (H * dh, d))}

        def block(i, key):
            k = jax.random.split(key, 4)
            mix = {"attn": attention(k[0])} if c.attends(i) \
                else {"ssm": mixer(k[0])}
            return {"ln1": {"scale": jnp.ones((d,), dt)}, **mix,
                    "ln2": {"scale": jnp.ones((d,), dt)},
                    "mlp": {"gate": normal(k[1], (d, c.d_ffn)),
                            "up": normal(k[2], (d, c.d_ffn)),
                            "down": normal(k[3], (c.d_ffn, d))}}

        keys = jax.random.split(rng, c.num_layers + 1)
        # the embedding's rows at std / embedding_multiplier: the
        # multiplier brings the stream's first value to the scale every
        # matrix has.  At std itself a row's own logit — 12 |e|^2 through
        # the tied head — stands ten standard deviations above the rest
        # and every greedy token repeats the one before it, whatever the
        # layers compute
        return {"wte": normal(keys[0], (c.vocab_size, d))
                / jnp.asarray(c.embedding_multiplier, dt),
                "blocks": [block(i, k) for i, k in enumerate(keys[1:])],
                "ln_f": {"scale": jnp.ones((d,), dt)}}

    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, vocab] float32, no
        cache: every state-space layer scans the whole sequence from a
        state of zeros."""
        c, spec = self.config, self.layer_spec()
        B, S = tokens.shape
        chunk = min(c.ssm_chunk, S)
        pad = -S % chunk
        x = params["wte"][tokens].astype(jnp.float32) * \
            c.embedding_multiplier
        pos = jnp.arange(S)
        causal = jnp.broadcast_to(pos[None, :] <= pos[:, None], (B, S, S))
        positions = jnp.broadcast_to(pos, (B, S))
        for i, p in enumerate(params["blocks"]):
            h = rms_norm_plain(x, p["ln1"], c.rms_norm_eps)
            if c.attends(i):
                q, k, v = project_grouped(c, p["attn"], h, positions, False,
                                          c.param_dtype)
                mixed = matmul32(attend_grouped(
                    q, k, v, causal, scale=c.attention_multiplier),
                    p["attn"]["o"])
            else:
                mixed, _, _ = ssm_mix(
                    spec, p["ssm"], jnp.pad(h, ((0, 0), (0, pad), (0, 0))),
                    jnp.zeros((B, c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                              jnp.float32),
                    jnp.zeros((B, c.ssm_conv - 1, c.conv_width),
                              c.param_dtype),
                    jnp.full((B,), S, jnp.int32))
                mixed = mixed[:, :S]
            x = x + c.residual_multiplier * mixed
            h = rms_norm_plain(x, p["ln2"], c.rms_norm_eps)
            x = x + c.residual_multiplier * silu_gated_ffn(p["mlp"], h)
        h = rms_norm_plain(x, params["ln_f"], c.rms_norm_eps)
        return matmul32(h, params["wte"].T) / c.logits_scaling

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
