"""Mixture-of-Experts layer with expert parallelism.

BEYOND-PARITY: the reference (v0.3.15) has no MoE (SURVEY.md §2.2 "EP:
absent"); upstream DeepSpeed grew deepspeed.moe later. Built TPU-first:

* experts are STACKED on a leading dim [E, ...] and sharded over the
  `data` mesh axis (DeepSpeed-style expert parallelism: EP group == DP
  group).  On a PR-4 factored mesh with `comm.moe` inner placement the
  expert dim rides `data_inner` only (replicated across outer groups)
  so the token exchange never leaves the fast fabric.
* TWO dispatch engines selected by the process-global wire config
  (moe/dispatch.py, the `"comm": {"moe": ...}` block):
  - "dense" (default, the seed path): GShard one-hot dispatch/combine
    tensors + einsum token movement — O(N·E·C·D), exchange implicit.
  - "sorted": fused sort-based dispatch — tokens argsorted by expert
    id, capacity-bucketed via segment positions (optionally dropless
    through a second-pass overflow bucket), moved by gather/scatter
    permutes — O(N log N + k·N·D), optionally over an EXPLICIT
    quantized all-to-all wire with per-level dtypes.
  Both engines share ONE routing core (dispatch.topk_routing), so
  expert choice, gate weights and capacity drops are identical.
* load-balancing aux loss (Switch Transformer eq. 4) returned alongside
  the output for the model to add to its objective.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comm.mesh import DATA_AXIS
from . import dispatch as _dsp


@dataclasses.dataclass
class MoEConfig:
    d_model: int
    d_ff: int
    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_std: float = 1e-2   # jitter on gate logits during training

    def __post_init__(self):
        if self.top_k > self.num_experts:
            raise ValueError(
                f"top_k ({self.top_k}) cannot exceed num_experts "
                f"({self.num_experts}): after masking every expert once, "
                f"further rounds would re-route to expert 0")


def top_k_gating(logits, k: int, capacity: int, rng=None,
                 noise_std: float = 0.0):
    """GShard top-k gating with capacity (the dense one-hot form).

    logits: [N, E] -> (combine [N, E, C] fp32, dispatch [N, E, C] bool,
    aux_loss scalar). Tokens beyond an expert's capacity are dropped
    (their combine weights are zero -> residual passthrough upstream).
    Routing (expert choice, queue positions, drops) comes from the
    shared sort-based core — positions in exact int32, not the seed's
    fp32 cumsum."""
    N, E = logits.shape
    if rng is not None and noise_std > 0.0:
        logits = logits + noise_std * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    eidx, gate, pos, keep, aux = _dsp.topk_routing(probs, k, capacity)

    combine = jnp.zeros((N, E, capacity), jnp.float32)
    dispatch = jnp.zeros((N, E, capacity), bool)
    for r in range(k):
        onehot = jax.nn.one_hot(eidx[r], E, dtype=jnp.float32)   # [N, E]
        slot = jax.nn.one_hot(jnp.where(keep[r], pos[r], capacity),
                              capacity + 1,
                              dtype=jnp.float32)[:, :capacity]   # [N, C]
        contrib = onehot[:, :, None] * slot[:, None, :]
        combine = combine + (gate[r] * keep[r])[:, None, None] * contrib
        dispatch = jnp.logical_or(dispatch, contrib > 0)
    return combine, dispatch, aux


class MoE:
    """Functional MoE FFN: __call__(params, x, rng, train) -> (y, aux)."""

    def __init__(self, config: MoEConfig):
        self.config = config

    def init(self, rng, param_dtype=jnp.float32):
        cfg = self.config
        k1, k2, k3 = jax.random.split(rng, 3)
        d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
        n = lambda k, s, sd: (sd * jax.random.normal(k, s)).astype(param_dtype)
        return {
            "gate": {"w": n(k1, (d, E), 0.02)},
            "experts": {
                "w1": n(k2, (E, d, f), d ** -0.5),
                "b1": jnp.zeros((E, f), param_dtype),
                "w2": n(k3, (E, f, d), f ** -0.5),
                "b2": jnp.zeros((E, d), param_dtype),
            },
        }

    @staticmethod
    def param_specs():
        """Expert-parallel: the expert dim rides the data axis.  (Under
        `comm.moe` inner placement on a factored mesh the runtime's
        sharding plan narrows the translation of this logical axis to
        `data_inner` — zero/partition.py — keeping these specs
        layout-agnostic.)"""
        return {
            "gate": {"w": P()},
            "experts": {"w1": P(DATA_AXIS, None, None),
                        "b1": P(DATA_AXIS, None),
                        "w2": P(DATA_AXIS, None, None),
                        "b2": P(DATA_AXIS, None)},
        }

    def capacity(self, tokens_per_group: int, train: bool) -> int:
        """Per-expert slot count for one token group.  CEILING division:
        the seed's int() truncation dropped tokens in small groups even
        at capacity_factor >= 1.0 (e.g. S=6, E=4, factor=1.25 -> 1.875
        truncated to 1 slot while a balanced top-1 routing needs 2)."""
        cfg = self.config
        factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
        cap = int(math.ceil(factor * tokens_per_group * cfg.top_k /
                            max(cfg.num_experts, 1) - 1e-9))
        return max(cap, cfg.min_capacity)

    def __call__(self, params, x, rng=None, train=True
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Grouped (GShard-style) dispatch: gating runs per batch row, so
        per-row buckets have C ~ S/E — memory linear in tokens (a single
        global group would make them quadratic)."""
        cfg = self.config
        wcfg = _dsp.get_wire_config()
        B, S, D = x.shape
        cap = self.capacity(S, train)
        noise = cfg.noisy_gate_std if (train and rng is not None) else 0.0
        keys = (jax.random.split(rng, B) if noise > 0.0
                else jnp.zeros((B, 2), jnp.uint32))

        if wcfg.dispatch == "sorted":
            engaged = _dsp.wire_engagement(wcfg, cfg.num_experts, B)
            if engaged is not None:
                return self._sorted_wire(params, x, keys, noise, cap,
                                         train, wcfg, *engaged)
            return self._sorted_local(params, x, keys, noise, cap,
                                      train, wcfg)
        return self._dense(params, x, keys, noise, cap, train)

    # -- shared pieces -------------------------------------------------

    def _route(self, logits, key, noise, cap):
        """Per-row routing: noisy logits -> shared sort-based core."""
        cfg = self.config
        if noise > 0.0:
            logits = logits + noise * jax.random.normal(key, logits.shape)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return _dsp.topk_routing(probs, cfg.top_k, cap)

    def _expert_ffn(self, expert_in, params, dtype):
        """[E, B, C, D] expert compute — the SAME einsums on both
        dispatch engines, so parity reduces to the token movement."""
        w1 = params["experts"]["w1"].astype(dtype)
        b1 = params["experts"]["b1"].astype(dtype)
        w2 = params["experts"]["w2"].astype(dtype)
        b2 = params["experts"]["b2"].astype(dtype)
        h = jnp.einsum("ebcd,edf->ebcf", expert_in, w1) + \
            b1[:, None, None, :]
        h = jax.nn.gelu(h, approximate=True)
        return jnp.einsum("ebcf,efd->ebcd", h, w2) + b2[:, None, None, :]

    # -- dense one-hot engine (the seed path, byte-for-byte) -----------

    def _dense(self, params, x, keys, noise, cap, train):
        cfg = self.config
        logits = jnp.einsum("bsd,de->bse", x,
                            params["gate"]["w"].astype(x.dtype))
        combine, dispatch, aux = jax.vmap(
            lambda lg, k: top_k_gating(lg, cfg.top_k, cap,
                                       rng=k if noise > 0.0 else None,
                                       noise_std=noise))(logits, keys)
        aux = jnp.mean(aux)
        # dispatch: [B,S,E,C] x [B,S,D] -> [E,B,C,D] (all_to_all under
        # sharding: tokens sharded over data, experts sharded over data)
        expert_in = jnp.einsum("bsec,bsd->ebcd",
                               dispatch.astype(x.dtype), x)
        expert_out = self._expert_ffn(expert_in, params, x.dtype)
        # combine: [B,S,E,C] x [E,B,C,D] -> [B,S,D]
        y = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), expert_out)
        return y, aux.astype(jnp.float32)

    # -- sorted (fused permute) engine, implicit exchange --------------

    def _sorted_local(self, params, x, keys, noise, cap, train, wcfg):
        cfg = self.config
        B, S, D = x.shape
        E = cfg.num_experts
        logits = jnp.einsum("bsd,de->bse", x,
                            params["gate"]["w"].astype(x.dtype))
        eidx, gate, pos, keep, aux = jax.vmap(
            lambda lg, k: self._route(lg, k, noise, cap))(logits, keys)
        aux = jnp.mean(aux)
        expert_in = jax.vmap(
            lambda xr, er, pr, kr: _dsp.sorted_dispatch(xr, er, pr, kr,
                                                        E, cap)
        )(x, eidx, pos, keep)                       # [B, E, C, D]
        expert_out = self._expert_ffn(expert_in.transpose(1, 0, 2, 3),
                                      params, x.dtype)
        out = expert_out.transpose(1, 0, 2, 3)      # [B, E, C, D]
        y = jax.vmap(_dsp.sorted_combine)(out, eidx, gate, pos, keep)

        dropped = jnp.sum(~keep)
        if wcfg.dropless:
            ov_cap = _dsp.overflow_capacity(cfg.top_k, S,
                                            wcfg.overflow_factor)
            w1 = params["experts"]["w1"].astype(x.dtype)
            b1 = params["experts"]["b1"].astype(x.dtype)
            w2 = params["experts"]["w2"].astype(x.dtype)
            b2 = params["experts"]["b2"].astype(x.dtype)

            def row_overflow(xr, er, gr, pr, kr):
                buf, ov_e, ov_keep, ov_dest = _dsp.overflow_dispatch(
                    xr, er, pr, kr, ov_cap)
                ov_out = _dsp.overflow_ffn(buf, ov_e, w1, b1, w2, b2)
                y_ov = _dsp.overflow_combine(ov_out, gr, ov_keep,
                                             ov_dest, S)
                return y_ov, jnp.sum(kr.reshape(-1) | ov_keep)

            y_ov, served = jax.vmap(row_overflow)(x, eidx, gate, pos, keep)
            y = y + y_ov
            dropped = B * cfg.top_k * S - jnp.sum(served)
        if wcfg.counters:
            _dsp.record_dispatch_stats(dropped, jnp.sum(keep),
                                       B * E * cap)
        return y, aux.astype(jnp.float32)

    # -- sorted engine over the explicit all-to-all wire ---------------

    def _sorted_wire(self, params, x, keys, noise, cap, train, wcfg,
                     mesh_info, axes):
        cfg = self.config
        B, S, D = x.shape
        E = cfg.num_experts
        dp = mesh_info.axis_size(DATA_AXIS)
        plan = _dsp.build_a2a_plan(wcfg, mesh_info, E, B // dp, cap, D)
        ep = plan.ep
        El = E // ep
        grid = tuple(mesh_info.axis_size(a) for a in axes)  # hop worlds
        data_spec = mesh_info.data_spec
        expert_spec = axes[0] if len(axes) == 1 else tuple(axes)

        gate_w = params["gate"]["w"]
        experts = params["experts"]

        def body(gw, ex, xl, keysl):
            Bl = xl.shape[0]
            logits = jnp.einsum("bsd,de->bse", xl, gw.astype(xl.dtype))
            eidx, gate, pos, keep, aux = jax.vmap(
                lambda lg, k: self._route(lg, k, noise, cap))(logits, keysl)
            expert_in = jax.vmap(
                lambda xr, er, pr, kr: _dsp.sorted_dispatch(
                    xr, er, pr, kr, E, cap))(xl, eidx, pos, keep)
            buf = expert_in.transpose(1, 0, 2, 3)       # [E, Bl, C, D]
            buf = buf.reshape(grid + (El, Bl, cap, D))
            buf = _dsp.wire_all_to_all(buf, plan, reverse=False,
                                       record=wcfg.counters)
            # leading grid dims now index SOURCE ranks, rank-major
            buf = buf.reshape(ep, El, Bl, cap, D)
            buf = buf.transpose(1, 0, 2, 3, 4).reshape(El, ep * Bl,
                                                       cap, D)
            out = self._expert_ffn(buf, {"experts": {
                k: v.astype(xl.dtype) for k, v in ex.items()}}, xl.dtype)
            out = out.reshape(El, ep, Bl, cap, D).transpose(1, 0, 2, 3, 4)
            out = out.reshape(grid + (El, Bl, cap, D))
            out = _dsp.wire_all_to_all(out, plan, reverse=True,
                                       record=wcfg.counters)
            out = out.reshape(E, Bl, cap, D).transpose(1, 0, 2, 3)
            y = jax.vmap(_dsp.sorted_combine)(out, eidx, gate, pos, keep)
            if wcfg.counters:
                _dsp.record_dispatch_stats(jnp.sum(~keep), jnp.sum(keep),
                                           Bl * E * cap)
            return y, aux

        expert_in_specs = {"w1": P(expert_spec, None, None),
                           "b1": P(expert_spec, None),
                           "w2": P(expert_spec, None, None),
                           "b2": P(expert_spec, None)}
        axis_names = mesh_info.manual_axes(mesh_info.data_axes)
        smapped = jax.shard_map(
            body, mesh=mesh_info.mesh,
            in_specs=(P(), expert_in_specs, P(data_spec, None, None),
                      P(data_spec, None)),
            out_specs=(P(data_spec, None, None), P(data_spec)),
            axis_names=axis_names, check_vma=False)
        y, aux = smapped(gate_w, experts, x, keys)
        return y, jnp.mean(aux).astype(jnp.float32)
