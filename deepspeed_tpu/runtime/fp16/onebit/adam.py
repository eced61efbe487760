"""1-bit Adam — error-compensated sign-compressed momentum allreduce.

Reference: deepspeed/runtime/fp16/onebit/adam.py:14 + the NCCL/MPI compressed
backends (runtime/comm/nccl.py:47-186). Semantics kept: dense Adam during a
`freeze_step` warmup, then the second moment is frozen and only momentum is
communicated, 1-bit sign-compressed with worker- and server-side error
feedback.

TPU redesign: the reference's cupy packbits + all_to_all + allgather
machinery was a bandwidth workaround for commodity interconnects. Here the
compress -> reduce -> recompress pipeline is a pure function inside the
jitted step: signs ride a psum over the `data` mesh axis (ICI), and both
error-feedback stages live in optimizer state. The optimizer owns its DP
reduction (`handles_dp_reduction`), so the engine skips its gradient psum
after warmup.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# the compress->reduce->recompress pipeline lives in runtime/comm
# (shared with OnebitLamb and the standalone CompressedBackend)
from ...comm.compressed import (compressed_allreduce,  # noqa: E402,F401
                                int8_compressed_allreduce)


class OnebitAdam:
    name = "OnebitAdam"
    handles_dp_reduction = True

    def __init__(self, params=None, deepspeed=None, lr=1e-3, freeze_step=100000,
                 bias_correction=True, betas=(0.9, 0.999), eps=1e-8,
                 eps_inside_sqrt=False, weight_decay=0.0, max_grad_norm=0.0,
                 amsgrad=False, cuda_aware=False, wire="sign"):
        if amsgrad:
            raise RuntimeError("1-bit Adam does not support the AMSGrad variant.")
        if wire not in ("sign", "int8"):
            raise ValueError(f"wire must be 'sign' or 'int8', got {wire!r}")
        self.defaults = dict(lr=lr, betas=betas, eps=eps,
                             weight_decay=weight_decay,
                             bias_correction=bias_correction)
        self.param_groups = [dict(self.defaults)]
        self.freeze_step = int(freeze_step)
        self.eps_inside_sqrt = eps_inside_sqrt
        # wire="int8": quantized all_to_all/allgather instead of sign
        # compression — the variant whose wire bytes XLA actually shrinks
        # (~4x vs fp32; sign rides pmean at full width)
        self.wire = wire

    @property
    def lr(self):
        return self.param_groups[0]["lr"]

    def init(self, params):
        zeros = lambda p: jnp.zeros(p.shape, dtype=jnp.float32)
        zt = lambda: jax.tree_util.tree_map(zeros, params)
        return {
            "step": jnp.zeros((), dtype=jnp.int32),
            "exp_avg": zt(),
            "exp_avg_sq": zt(),
            "worker_error": zt(),
            "server_error": zt(),
        }

    def update(self, grads, state, params, lr=None, comm_axis=None):
        """grads must be LOCAL (per-shard, unreduced) gradients; this
        optimizer performs its own DP averaging (dense during warmup,
        compressed after)."""
        g = self.param_groups[0]
        lr = g["lr"] if lr is None else lr
        beta1, beta2 = g["betas"]
        eps = g["eps"]
        wd = g["weight_decay"]
        step = state["step"] + 1
        frozen = step > self.freeze_step  # traced scalar bool

        if g["bias_correction"]:
            bc1 = 1.0 - beta1 ** step.astype(jnp.float32)
            bc2 = 1.0 - beta2 ** step.astype(jnp.float32)
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)

        def moments(grad, m, we, se):
            """FLAT (single fused buffer) momentum update: the reference
            NCCL backend also compresses one flattened momentum buffer
            (grouped per-2048 scales), paying each collective's latency
            once per step instead of once per leaf. Only the COMMUNICATED
            buffers (m, grad, errors) flatten; v stays per-leaf outside
            the cond (it is untouched after freeze). Returns
            (m_new, g_reduced, we_new, se_new) — g_reduced is the dense
            mean during warmup (feeds the per-leaf v update) and zeros
            after freeze (v frozen)."""

            def warm_branch(operands):
                grad_, m_, we_, se_ = operands
                g_ = lax.pmean(grad_, comm_axis) if comm_axis is not None else grad_
                m_warm = beta1 * m_ + (1.0 - beta1) * g_
                return m_warm, g_, we_, se_

            def frozen_branch(operands):
                grad_, m_, we_, se_ = operands
                m_local = beta1 * m_ + (1.0 - beta1) * grad_
                reduce_fn = (int8_compressed_allreduce
                             if self.wire == "int8"
                             else compressed_allreduce)
                m_comp, we_new, se_new = reduce_fn(m_local, we_, se_,
                                                   comm_axis)
                return m_comp, jnp.zeros_like(grad_), we_new, se_new

            # lax.cond so only ONE communication path executes per step —
            # after freeze the dense allreduce must not run, or 1-bit's
            # bandwidth saving is negated.
            return lax.cond(
                frozen, frozen_branch, warm_branch, (grad, m, we, se))

        def upd(p, new_m, new_v):
            p32 = p.astype(jnp.float32)
            # bias corrections apply during warmup only: after freeze the
            # reference uses the CONSTANT denominator exp_avg_sq.sqrt()+eps
            # (1-bit adam.py step) — a still-growing 1/bc2 on a frozen v
            # would act as an unintended lr ramp through the compressed
            # stage
            bc1_eff = jnp.where(frozen, 1.0, bc1)
            bc2_eff = jnp.where(frozen, 1.0, bc2)
            if self.eps_inside_sqrt:
                denom = jnp.sqrt(new_v / bc2_eff + eps)
            else:
                denom = jnp.sqrt(new_v / bc2_eff) + eps
            step_val = (new_m / bc1_eff) / denom
            if wd:
                step_val = step_val + wd * p32
            return (p32 - lr * step_val).astype(p.dtype)

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        gl = treedef.flatten_up_to(grads)
        ml = treedef.flatten_up_to(state["exp_avg"])
        vl = treedef.flatten_up_to(state["exp_avg_sq"])
        wel = treedef.flatten_up_to(state["worker_error"])
        sel = treedef.flatten_up_to(state["server_error"])

        flat = lambda ls: jnp.concatenate(
            [l.astype(jnp.float32).ravel() for l in ls])
        new_fm, fgred, new_fwe, new_fse = moments(
            flat(gl), flat(ml), flat(wel), flat(sel))

        def split(fvec):
            out, off = [], 0
            for p in p_leaves:
                out.append(fvec[off:off + p.size].reshape(p.shape))
                off += p.size
            return out

        nm, gred = split(new_fm), split(fgred)
        # v per leaf, outside the cond: frozen -> unchanged (gred is 0
        # there, but where() keeps the exact old buffer)
        nv = [jnp.where(frozen, v_, beta2 * v_ + (1.0 - beta2) * g_ * g_)
              for v_, g_ in zip(vl, gred)]
        new_p = [upd(p, m_, v_) for p, m_, v_ in zip(p_leaves, nm, nv)]
        unflat = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        return unflat(new_p), {"step": step, "exp_avg": unflat(nm),
                               "exp_avg_sq": unflat(nv),
                               "worker_error": unflat(split(new_fwe)),
                               "server_error": unflat(split(new_fse))}

    def state_dict(self):
        return {"param_groups": self.param_groups,
                "freeze_step": self.freeze_step}

    def load_state_dict(self, sd):
        self.param_groups = sd["param_groups"]
        self.freeze_step = sd.get("freeze_step", self.freeze_step)
