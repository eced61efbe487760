"""Qwen3-Next (`model_type` `qwen3_next`): gated delta-rule
linear-attention mixers that keep a MATRIX state a request, beside a few
gated softmax-attention layers with partial rotary positions, and
softmax-routed experts beside one shared expert behind a sigmoid gate.

A block is pre-norm and sequential, the residual stream float32:
x <- x + mixer(N x), x <- x + moe(N x), N(x) = x rsqrt(mean x^2 + eps)
(1 + w).  Layer l attends where (l + 1) % `period` == 0 and is a gated
delta mixer elsewhere.

Gated delta mixer (`gdn_mix`; Hk key heads of dk, Hv value heads of dv,
value head j on key head j // (Hv / Hk)), for the token at t with h_t
the normed stream:
  [q | k | v | z] = h_t W_qkvz           (Hk dk | Hk dk | Hv dv | Hv dv)
  [b | a] = h_t W_ba                     (Hv | Hv)
  [q | k | v] <- silu(sum_j w_c[:, j] [q | k | v]_{t - (K-1) + j})
                                         (K taps, causal, no bias)
  q^ = q / |q| dk^-1/2,  k^ = k / |k|    (eps 1e-6 under the root)
  beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)   (float32)
  S <- e^g S;  u = beta (v - S^T k^);  S <- S + k^ u^T;  o = S^T q^
                                         per value head, S [dk, dv] float32
  out_t = W_o (rmsnorm(o; a head's dv, gain w) * silu(z))
Such a layer keeps, for a request, S and the convolution's last K - 1
inputs and nothing else.  `gdn_mix` takes both and hands both back, moved
on by the call's valid positions: by the recurrence itself where the
call is one token (`delta_step`, through the kernel registry's
`gdn_step`: on a TPU the walk of the step's live slots,
kernels/gdn.py), by the chunked form where it is many (`delta_scan`,
`gdn_chunk` positions at a time: inside a chunk (I + tril(diag(beta)
Gamma * K K^T, -1))^-1 by forward substitution, Gamma the decay between
two positions of the chunk; the state passed from chunk to chunk).  A
position past the call's valid ones has g = 0 and beta = 0 — the state
passes it unchanged — and is not among the convolution's inputs kept.

Gated attention (`project_gated`): [q | gate] a head = h W_q (H heads of
2 Dh), k, v = h W_k, h W_v (KV heads of Dh); q and k RMS-normed over the
head (gain 1 + g); the first `rotary_dim` values of a head rotated,
half-split pairing; causal softmax at Dh^-1/2, query head n on K/V head
n // (H / KV); out = W_o (attn * sigmoid(gate)).  A token's cache row in
such a layer is its KV keys (normed, rotated) and values.

Experts: softmax over ALL `num_experts` router outputs in float32, the
`top_k` largest, weights over their sum; among the `experts_held` this
chip holds from `first_expert` on (moe/dropless.py); plus
sigmoid(h w_s) times the shared expert (models/cohere2_moe.py
`routed_ffn`, which reads the layer spec).  Untied head over the rows of
the vocabulary held.

The multi-token-prediction layer of the published model is not built.
The serving engine runs the model through `layer_spec()`
(serving/layers.py); `apply` is the uncached forward the tests compare
with `benchmarks/reference/qwen3_next.py`, which knows the recurrence
only.  Training it and a mesh are not built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import cohere2_moe
from .deepseek_v2 import rms_norm_plain
from .evabyte import matmul32, rms_norm, rope
from .layer_spec import LayerSpec

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936         # rows of the vocabulary held
    max_seq_len: int = 262144
    num_layers: int = 48
    period: int = 4                  # period - 1 delta layers, one full
    d_model: int = 2048
    num_heads: int = 16
    kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64             # partial_rotary_factor x head_dim
    rope_theta: float = 1e7
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    gdn_chunk: int = 64
    d_expert: int = 512
    d_shared: int = 512
    num_experts: int = 512           # the router's outputs
    top_k: int = 10
    experts_held: int = 0            # 0: every expert is held here
    first_expert: int = 0
    rms_norm_eps: float = 1e-6
    # seeded weights only: every matrix N(0, init_std) (the router's
    # N(0, router_std), 0: init_std); a head forgets over 1 / (A step)
    # tokens, -A = -exp(A_log) uniform in init_a and step =
    # softplus(dt_bias) log-uniform in init_dt: from a few tokens to
    # beyond the longest prompt
    init_std: float = 0.02
    router_std: float = 0.0
    init_a: tuple = (1.0, 16.0)
    init_dt: tuple = (2e-6, 2e-2)
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(f"num_heads {self.num_heads} must be a "
                             f"multiple of kv_heads ({self.kv_heads})")
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                f"gdn_value_heads {self.gdn_value_heads} must be a "
                f"multiple of gdn_key_heads ({self.gdn_key_heads})")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("rotary positions need an even rotary_dim of "
                             "at most head_dim")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} must lie in 1.."
                             f"num_experts ({self.num_experts})")
        if self.experts_held < 0 or self.first_expert < 0 or \
                self.first_expert + self.experts_held > self.num_experts:
            raise ValueError(
                f"a share of the experts is experts_held >= 0 experts from "
                f"first_expert on, inside the router's {self.num_experts}")
        if self.period < 1 or self.gdn_conv < 2:
            raise ValueError("period must be >= 1 and the convolution has "
                             "at least 2 taps")

    @property
    def held(self) -> int:
        """Routed experts whose matrices are here."""
        return self.experts_held or self.num_experts

    @property
    def key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def value_width(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_width(self) -> int:
        """Channels of the convolution: [q | k | v]."""
        return 2 * self.key_width + self.value_width

    def attends(self, layer: int) -> bool:
        return (layer + 1) % self.period == 0


# ---------------------------------------------------------------------------
# the gated delta rule (shared with serving/layers.py)
# ---------------------------------------------------------------------------


def delta_step(q, k, v, g, beta, state):
    """The recurrence, one token a sequence: q^, k^ [B, H, dk] (normed,
    q^ scaled), v [B, H, dv], g, beta [B, H] (both 0: the state passes
    unchanged), state [B, H, dk, dv], all float32 -> (o [B, H, dv],
    state).  No term crosses sequences."""
    state = state * jnp.exp(g)[:, :, None, None]
    mem = jnp.sum(state * k[..., None], axis=-2)             # S^T k^
    u = beta[..., None] * (v - mem)
    state = state + k[..., None] * u[:, :, None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _solve_unit_lower(a):
    """(I + a)^-1 for a [..., C, C] strictly lower triangular, by forward
    substitution over blocks: the inverses of the
    diagonal blocks of b rows give those of 2 b rows — below the
    diagonal, -(D_2 a_21 D_1) — from single rows up, log2 C times two
    batched products (a row at a time, 64 dependent updates of the whole
    array were a sixth of a prefill chunk: PERF.md section 6, PR 57)."""
    size, lead = a.shape[-1], a.shape[:-2]
    C = 1 << (size - 1).bit_length()     # rows of zeros up to a power of 2
    a = jnp.pad(a, [(0, 0)] * len(lead) + [(0, C - size)] * 2)
    inv = jnp.ones(lead + (C, 1, 1), a.dtype)        # C blocks of one row
    b = 1
    while b < C:
        m = C // (2 * b)
        # the m diagonal blocks of 2 b rows, and their lower left quarter
        blocks = jnp.moveaxis(jnp.diagonal(
            a.reshape(lead + (m, 2 * b, m, 2 * b)), axis1=-4, axis2=-2),
            -1, -3)
        d = inv.reshape(lead + (m, 2, b, b))
        d1, d2 = d[..., 0, :, :], d[..., 1, :, :]
        low = -jnp.matmul(jnp.matmul(d2, blocks[..., b:, :b],
                                     precision=HIGHEST),
                          d1, precision=HIGHEST)
        inv = jnp.concatenate(
            [jnp.concatenate([d1, jnp.zeros_like(d1)], axis=-1),
             jnp.concatenate([low, d2], axis=-1)], axis=-2)
        b *= 2
    return inv.reshape(lead + (C, C))[..., :size, :size]


def delta_scan(q, k, v, g, beta, state, chunk: int):
    """The same recurrence over T positions, `chunk` at a time: q^, k^
    [B, T, H, dk], v [B, T, H, dv], g, beta [B, T, H], state
    [B, H, dk, dv] -> (o [B, T, H, dv], state after the last position).
    T is a multiple of `chunk`; float32 at full precision."""
    B, T, H, dk = q.shape
    n = T // chunk
    mm = lambda eq, *a: jnp.einsum(eq, *a, precision=HIGHEST)

    def chunks(t):              # [B, T, H, ...] -> [n, B, H, chunk, ...]
        t = t.reshape((B, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g), chunks(beta)                         # [n, B, H, C]
    cum = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # position s as position t sees it, s <= t: decayed by e^(cum_t - cum_s)
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    # what every position of a chunk adds, all chunks at once: the state
    # that enters a chunk is not needed for it
    solve = _solve_unit_lower(jnp.where(
        jnp.tril(lower, -1), mm("...td,...sd->...ts", kb, k) * decay, 0.0))
    w = mm("...ts,...sd->...td", solve, v * beta[..., None])
    kd = mm("...ts,...sd->...td", solve, kb * jnp.exp(cum)[..., None])
    qk = mm("...td,...sd->...ts", q, k) * decay               # s <= t

    def one(state, inp):
        q, k, w, kd, qk, cum = inp
        u = w - mm("bhtk,bhkv->bhtv", kd, state)
        o = mm("bhtk,bhkv->bhtv", q * jnp.exp(cum)[..., None], state) + \
            mm("bhts,bhsv->bhtv", qk, u)
        tail = jnp.exp(cum[..., -1:] - cum)                   # [B, H, C]
        state = state * jnp.exp(cum[..., -1])[..., None, None] + \
            mm("bhtk,bhtv->bhkv", k * tail[..., None], u)
        return state, o

    state, o = jax.lax.scan(one, state, (q, k, w, kd, qk, cum))
    # [n, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape(B, T, H, -1), state


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gdn_mix(spec, p, h, state, conv, n_valid, live=None):
    """The gated delta mixer over h [B, T, D] (normed) from a request's
    `state` [B, Hv, dk, dv] float32 and the convolution's last inputs
    `conv` [B, K - 1, conv_width]; `n_valid` [B]: how many of the T
    positions are real.  -> (out [B, T, D] float32, state, conv), both
    moved on by the valid positions and by nothing else.  Where T is 1
    the recurrence goes through the kernel registry's `gdn_step`, which
    may walk `live` — `kernels/ssm.py::live_slots(n_valid)`, worked out
    here unless the caller has it for all its layers — and leave every
    other sequence's state where it lies."""
    B, T, _ = h.shape
    Hk, Hv = spec.gdn_key_heads, spec.gdn_value_heads
    dk, dv, K = spec.gdn_key_dim, spec.gdn_value_dim, spec.gdn_conv
    kw, vw = Hk * dk, Hv * dv
    qkv, z = jnp.split(matmul32(h, p["qkvz"]), [2 * kw + vw], axis=-1)
    b, a = jnp.split(matmul32(h, p["ba"]), 2, axis=-1)        # [B, T, Hv]
    # the convolution's inputs at the dtype they are kept in between
    # calls: where a call ends must not show
    seq = jnp.concatenate(
        [conv, qkv.astype(conv.dtype)], axis=1).astype(jnp.float32)
    w = p["conv_w"].astype(jnp.float32)                       # [conv, K]
    c = jax.nn.silu(sum(seq[:, j:j + T] * w[:, j] for j in range(K)))
    # the last K - 1 VALID inputs: rows n_valid .. of [kept | call]
    conv = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
        s, n, K - 1, axis=0))(seq, n_valid).astype(conv.dtype)
    q, k, v = jnp.split(c, [kw, 2 * kw], axis=-1)
    # value head j reads key head j // (Hv / Hk)
    heads = lambda t: jnp.repeat(t.reshape(B, T, Hk, dk), Hv // Hk, axis=2)
    q, k = _l2norm(heads(q)) * dk ** -0.5, _l2norm(heads(k))
    v = v.reshape(B, T, Hv, dv)
    valid = (jnp.arange(T)[None, :] < n_valid[:, None])[..., None]
    beta = jnp.where(valid, jax.nn.sigmoid(b), 0.0)
    g = jnp.where(valid, -jnp.exp(p["A_log"].astype(jnp.float32))
                  * jax.nn.softplus(a + p["dt_bias"].astype(jnp.float32)),
                  0.0)
    if T == 1:
        from ..kernels import registry
        from ..kernels.gdn import gdn_step_info
        from ..kernels.ssm import live_slots

        o, state = registry.dispatch(
            "gdn_step", q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
            state, *(live_slots(n_valid) if live is None else live),
            info=gdn_step_info(state))
        o = o[:, None]
    else:
        o, state = delta_scan(q, k, v, g, beta, state,
                              min(spec.gdn_chunk, T))
    # the norm over a head's dv values, then the gate
    o = rms_norm_plain(o, p["norm"], spec.eps).reshape(B, T, vw) * \
        jax.nn.silu(z)
    return matmul32(o, p["out"]), state, conv


# ---------------------------------------------------------------------------
# gated attention (shared with serving/layers.py)
# ---------------------------------------------------------------------------


def rope_partial(x, positions, theta, rotary_dim: int, halves: bool):
    """Rotary positions over the first `rotary_dim` values of a head (0:
    all of it), half-split pairing where `halves`, else interleaved.
    x [..., T, H, Dh], positions [..., T] -> float32."""
    turn = rope if halves else cohere2_moe.rope_interleaved
    x = x.astype(jnp.float32)
    if not rotary_dim or rotary_dim == x.shape[-1]:
        return turn(x, positions, theta)
    return jnp.concatenate(
        [turn(x[..., :rotary_dim], positions, theta), x[..., rotary_dim:]],
        axis=-1)


def project_gated(cfg, spec, p, h, positions, rotate: bool, dtype):
    """h [B, T, D] at positions [B, T] -> (q [B, T, H, Dh], k, v
    [B, T, KV, Dh] at `dtype`, gate [B, T, H * Dh] float32 or None):
    `cohere2_moe.project_grouped` with what the spec adds — with
    `attn_gate` the query projection is [q | gate] a head; with
    `qk_norm` q and k are RMS-normed over the head first (the gain as
    `spec.norm` has it: 1 + g, or under "rmsnorm" the plain w); a rotating
    layer turns `rotary_dim` values of a head, pairing by
    `rope_halves`."""
    B, T, _ = h.shape
    H, KV, Dh = cfg.num_heads, spec.kv_heads, cfg.head_dim
    q, gate = matmul32(h, p["q"]), None
    if spec.attn_gate:
        q, gate = jnp.split(q.reshape(B, T, H, 2 * Dh), 2, axis=-1)
        gate = gate.reshape(B, T, H * Dh)
    q = q.reshape(B, T, H, Dh)
    k = matmul32(h, p["k"]).reshape(B, T, KV, Dh)
    v = matmul32(h, p["v"]).reshape(B, T, KV, Dh)
    if spec.qk_norm:
        norm = rms_norm_plain if spec.norm == "rmsnorm" else rms_norm
        q = norm(q, p["q_norm"], spec.eps)
        k = norm(k, p["k_norm"], spec.eps)
    if rotate:
        q = rope_partial(q, positions, spec.rope_theta, spec.rotary_dim,
                         spec.rope_halves)
        k = rope_partial(k, positions, spec.rope_theta, spec.rotary_dim,
                         spec.rope_halves)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), gate


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class Qwen3Next:
    """Qwen3-Next's language model as the serving engine and the tests
    take it: `init` from a key, `apply` (uncached), `layer_spec` (what
    serving builds its programs from)."""

    def __init__(self, config: Qwen3NextConfig):
        self.config = config

    def layer_spec(self) -> LayerSpec:
        c = self.config
        attends = [c.attends(i) for i in range(c.period)]
        return LayerSpec(
            norm="rmsnorm_unit_offset", positions="per_layer",
            attention="grouped", ffn="routed_experts", head="untied",
            eps=c.rms_norm_eps, rope_theta=c.rope_theta, top_k=c.top_k,
            kv_heads=c.kv_heads,
            layer_positions=tuple("rope" if a else "none" for a in attends),
            layer_mixers=tuple("attention" if a else "gdn" for a in attends),
            scoring="softmax", renormalize=True, shared="gated",
            experts_held=c.experts_held, first_expert=c.first_expert,
            gdn_key_heads=c.gdn_key_heads, gdn_value_heads=c.gdn_value_heads,
            gdn_key_dim=c.gdn_key_dim, gdn_value_dim=c.gdn_value_dim,
            gdn_conv=c.gdn_conv, gdn_chunk=c.gdn_chunk,
            attn_gate=True, qk_norm=True, rotary_dim=c.rotary_dim,
            rope_halves=True).validate()

    def init(self, rng):
        c = self.config
        d, dt, std = c.d_model, c.param_dtype, c.init_std
        H, KV, dh = c.num_heads, c.kv_heads, c.head_dim

        def normal(key, shape, scale=std):
            return (jax.random.normal(key, shape) * scale).astype(dt)

        def gated(keys, width, lead=()):
            return {"gate": normal(keys[0], lead + (d, width)),
                    "up": normal(keys[1], lead + (d, width)),
                    "down": normal(keys[2], lead + (width, d))}

        def uniform(key, lo, hi):
            return jax.random.uniform(key, (c.gdn_value_heads,), jnp.float32,
                                      lo, hi)

        def mixer(key):
            k = jax.random.split(key, 6)
            step = jnp.exp(uniform(k[4], *map(math.log, c.init_dt)))
            return {"qkvz": normal(k[0], (d, c.conv_width + c.value_width)),
                    "ba": normal(k[1], (d, 2 * c.gdn_value_heads)),
                    # the taps as a depthwise Conv1d draws them
                    "conv_w": jax.random.uniform(
                        k[2], (c.conv_width, c.gdn_conv), jnp.float32,
                        -c.gdn_conv ** -0.5, c.gdn_conv ** -0.5).astype(dt),
                    "A_log": jnp.log(uniform(k[3], *c.init_a)),
                    # the inverse softplus of the step
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "norm": {"scale": jnp.ones((c.gdn_value_dim,), dt)},
                    "out": normal(k[5], (c.value_width, d))}

        def attention(key):
            k = jax.random.split(key, 4)
            return {"q": normal(k[0], (d, H * 2 * dh)),
                    "k": normal(k[1], (d, KV * dh)),
                    "v": normal(k[2], (d, KV * dh)),
                    "q_norm": {"scale": jnp.zeros((dh,), dt)},
                    "k_norm": {"scale": jnp.zeros((dh,), dt)},
                    "o": normal(k[3], (H * dh, d))}

        def block(i, key):
            k = jax.random.split(key, 9)
            mix = {"attn": attention(k[0])} if c.attends(i) \
                else {"gdn": mixer(k[0])}
            return {"ln1": {"scale": jnp.zeros((d,), dt)}, **mix,
                    "ln2": {"scale": jnp.zeros((d,), dt)},
                    "mlp": {"router": normal(k[1], (d, c.num_experts),
                                             c.router_std or std),
                            "experts": gated(k[2:5], c.d_expert, (c.held,)),
                            "shared": gated(k[5:8], c.d_shared),
                            "shared_gate": normal(k[8], (d, 1))}}

        keys = jax.random.split(rng, c.num_layers + 2)
        return {"wte": normal(keys[0], (c.vocab_size, d)),
                "blocks": [block(i, k) for i, k in enumerate(keys[2:])],
                "ln_f": {"scale": jnp.zeros((d,), dt)},
                "lm_head": normal(keys[1], (d, c.vocab_size))}

    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, vocab] float32, no
        cache: every delta layer scans the whole sequence from a state
        of zeros."""
        c, spec = self.config, self.layer_spec()
        B, S = tokens.shape
        chunk = min(c.gdn_chunk, S)
        pad = -S % chunk
        x = params["wte"][tokens].astype(jnp.float32)
        pos = jnp.arange(S)
        causal = jnp.broadcast_to(pos[None, :] <= pos[:, None], (B, S, S))
        positions = jnp.broadcast_to(pos, (B, S))
        for i, p in enumerate(params["blocks"]):
            h = rms_norm(x, p["ln1"], c.rms_norm_eps)
            if c.attends(i):
                q, k, v, gate = project_gated(
                    c, spec, p["attn"], h, positions, True, c.param_dtype)
                a = cohere2_moe.attend_grouped(q, k, v, causal)
                mixed = matmul32(a * jax.nn.sigmoid(gate), p["attn"]["o"])
            else:
                mixed, _, _ = gdn_mix(
                    spec, p["gdn"], jnp.pad(h, ((0, 0), (0, pad), (0, 0))),
                    jnp.zeros((B, c.gdn_value_heads, c.gdn_key_dim,
                               c.gdn_value_dim), jnp.float32),
                    jnp.zeros((B, c.gdn_conv - 1, c.conv_width),
                              c.param_dtype),
                    jnp.full((B,), S, jnp.int32))
                mixed = mixed[:, :S]
            x = x + mixed
            h = rms_norm(x, p["ln2"], c.rms_norm_eps)
            x = x + cohere2_moe.expert_ffn(spec, c, p["mlp"], h)[0]
        h = rms_norm(x, params["ln_f"], c.rms_norm_eps)
        return matmul32(h, params["lm_head"])

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
