"""PipelineEngine — scheduled 1F1B pipeline-parallel training.

Reference: deepspeed/runtime/pipe/engine.py:52 (train_batch :264,
eval_batch :351, the instruction dispatch table :1280-1306 executing
pipe/schedule.py's TrainSchedule). This engine executes the same ISA
(runtime/pipe/schedule.py) over heterogeneous LayerSpec stacks:

* each pipeline stage owns a contiguous slice of the PipelineModule's
  layers, placed on its own device group (a slice of `jax.devices()`),
  with the micro batch data-sharded inside the group;
* the TrainSchedule instruction streams of ALL stages are executed from
  the single controller in dependency order (a Recv is runnable once the
  matching Send has been issued). Dispatch is asynchronous, so stage
  programs overlap on-device exactly as the eager NCCL interpreter's do —
  the 1F1B warmup/steady/cooldown order and per-stage buffer counts
  (TrainSchedule.num_pipe_buffers) are preserved;
* BackwardPass recomputes the stage forward under jax.vjp from the saved
  buffer input (per-stage activation checkpointing — only the buffer
  inputs are held, the reference's activation_checkpoint_interval
  behaviour with interval = stage length);
* TiedLayerSpec params (reference pipe/module.py:415-428) are owned by
  their first stage; ReduceTiedGrads ships the other stages' tied grads
  to the owner and OptimizerStep re-broadcasts the updated copy;
* SendActivation/SendGrad are `jax.device_put` reshards onto the next
  stage's device group (the single-controller analogue of p2p.py:31-75);
  on real multi-chip topologies XLA rides ICI for these transfers.

The SPMD GPipe executor (parallel/pipeline.py) remains the
compile-everything alternative for homogeneous stacked blocks; this engine
is the general one: heterogeneous layers, tied weights, 1F1B buffering.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, Mesh

from ...utils.logging import log_dist, logger
from .. import checkpointing as ckpt_io
from ..engine import DeepSpeedEngine
from ..utils import has_overflow
from .compiler import (PipeInstrument, bind_program, compile_schedule,
                       schedule_occupancy)
from .module import PipelineModule, TiedLayerSpec
from .p2p import Channel, GlobalScalars, batch_shardable
from .schedule import (BackwardPass, ForwardPass, InterleavedTrainSchedule,
                       LoadMicroBatch, OptimizerStep, RecvActivation,
                       RecvGrad, ReduceGrads, ReduceTiedGrads,
                       SendActivation, SendGrad, TrainSchedule)


def _fsync_dir(path: str) -> None:
    """fsync a directory so a completed rename is durable — without this
    the file's rename can sit in the page cache after the data fsync,
    and a crash can publish `latest` over missing chunk files."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class _StageRuntime:
    """Per-stage state: params, device placement, jitted programs, buffers."""

    def __init__(self, stage_id: int, layers, specs, devices,
                 is_last: bool, loss_fn, compute_dtype):
        self.stage_id = stage_id
        self.layers = layers
        self.specs = specs
        self.devices = devices
        self.is_last = is_last
        self.loss_fn = loss_fn
        self.compute_dtype = compute_dtype
        self.mesh = Mesh(np.asarray(devices), ("data",))
        self.replicated = NamedSharding(self.mesh, PartitionSpec())
        self.batch_sharding = NamedSharding(self.mesh, PartitionSpec("data"))

        # owned params: {"layers": [...], "tied": {key: ...}} — set by engine
        self.own: Any = None
        self.ro_tied: Dict[str, Any] = {}   # read-only copies of tied params
        self.opt_state: Any = None
        self.acc: Any = None                # fp32 grad acc, same struct as own
        self.acc_ro: Dict[str, Any] = {}    # grads for non-owned tied params

        # pipeline buffers
        self.x_in: Dict[int, Any] = {}      # buffer -> stage input
        self.rng_in: Dict[int, Any] = {}    # buffer -> rng key used in fwd
        self.y_out: Dict[int, Any] = {}     # buffer -> stage output
        self.dx_out: Dict[int, Any] = {}    # buffer -> grad wrt stage input
        self.labels: Dict[int, Any] = {}    # micro-batch id -> labels (last)
        self.losses: List[Any] = []
        self.fwd_count = 0
        self.bwd_count = 0

        self._build_programs()

    # -- pure stage functions ------------------------------------------

    def _forward_fn(self, own, ro_tied, x, rng, train):
        dtype = self.compute_dtype
        cast = lambda t: jax.tree_util.tree_map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, t)
        own = cast(own)
        ro_tied = cast(ro_tied)
        tied = dict(own["tied"])
        tied.update(ro_tied)
        for layer, spec, p in zip(self.layers, self.specs, own["layers"]):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            if isinstance(spec, TiedLayerSpec):
                p = tied[spec.key]
                if spec.forward_fn is not None:
                    x = spec.forward_fn(layer, p, x)
                    continue
            x = layer.apply(p, x, rng=sub, train=train)
        return x

    def _build_programs(self):
        fwd = self._forward_fn

        def fwd_train(own, ro, x, rng):
            return fwd(own, ro, x, rng, True)

        def fwd_eval(own, ro, x, rng):
            return fwd(own, ro, x, rng, False)

        self.fwd_j = jax.jit(fwd_train)
        self.fwd_eval_j = jax.jit(fwd_eval)

        if self.is_last:
            loss_fn = self.loss_fn

            def loss_of(own, ro, x, labels, rng):
                out = fwd(own, ro, x, rng, True)
                return loss_fn(out, labels)

            def loss_j(own, ro, x, labels, rng):
                return loss_of(own, ro, x, labels, rng)

            def bwd_last(own, ro, x, labels, rng, scale, acc, acc_ro):
                def scaled(o, r, xx):
                    return loss_of(o, r, xx, labels, rng) * scale

                _, pull = jax.vjp(scaled, own, ro, x)
                d_own, d_ro, dx = pull(jnp.ones((), jnp.float32))
                f32 = lambda t: jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), t)
                new_acc = jax.tree_util.tree_map(jnp.add, acc, f32(d_own))
                new_ro = jax.tree_util.tree_map(jnp.add, acc_ro, f32(d_ro))
                return dx, new_acc, new_ro

            self.loss_j = jax.jit(loss_j)
            self.bwd_j = jax.jit(bwd_last, donate_argnums=(6, 7))

            def eval_loss(own, ro, x, labels, rng):
                out = fwd(own, ro, x, rng, False)
                return loss_fn(out, labels)

            self.eval_loss_j = jax.jit(eval_loss)
        else:
            def bwd_mid(own, ro, x, rng, dy, acc, acc_ro):
                def f(o, r, xx):
                    return fwd(o, r, xx, rng, True)

                _, pull = jax.vjp(f, own, ro, x)
                d_own, d_ro, dx = pull(dy)
                f32 = lambda t: jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), t)
                new_acc = jax.tree_util.tree_map(jnp.add, acc, f32(d_own))
                new_ro = jax.tree_util.tree_map(jnp.add, acc_ro, f32(d_ro))
                return dx, new_acc, new_ro

            self.bwd_j = jax.jit(bwd_mid, donate_argnums=(5, 6))

    def build_apply(self, optimizer, clip):
        def detect(acc, denom):
            sq = sum(jnp.sum(jnp.square(g / denom))
                     for g in jax.tree_util.tree_leaves(acc))
            return sq, has_overflow(acc)

        # one fused pass: squared grad norm (for global clipping) + local
        # overflow flag. The engine ORs the flags across stages BEFORE
        # apply, so an overflow anywhere skips the step everywhere —
        # per-stage skipping would desynchronize the stages' parameters
        # from the non-pipelined run (reference fp16 semantics: the whole
        # step is skipped)
        self.detect_j = jax.jit(detect)

        def apply_step(own, opt_state, acc, lr, denom, clip_coef, overflow):
            # clip_coef carries the GLOBAL-norm clipping factor (computed
            # across all stages by the engine) — per-stage local clipping
            # would change the update direction vs the non-pipelined run
            grads = jax.tree_util.tree_map(
                lambda g: g * (clip_coef / denom), acc)
            new_own, new_opt = optimizer.update(grads, opt_state, own, lr=lr)
            sel = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
            new_own = sel(new_own, own)
            new_opt = sel(new_opt, opt_state)
            zero = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return new_own, new_opt, zero

        self.apply_j = jax.jit(apply_step, donate_argnums=(0, 1, 2))

    # -- placement helpers ---------------------------------------------

    def place_replicated(self, tree):
        return jax.device_put(tree, self.replicated)

    def place_batch(self, x):
        x = jnp.asarray(x)
        if batch_shardable(x.shape, len(self.devices)):
            return jax.device_put(x, self.batch_sharding)
        return jax.device_put(x, self.replicated)

    def zero_acc(self):
        f32z = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), t)
        self.acc = self.place_replicated(f32z(self.own))
        self.acc_ro = self.place_replicated(f32z(self.ro_tied))


class PipelineEngine(DeepSpeedEngine):
    """Executes the TrainSchedule ISA over a staged PipelineModule.

    Public API matches the reference PipelineEngine: train_batch pulls
    gradient_accumulation_steps micro batches from the iterator and runs
    the full 1F1B schedule + optimizer step; eval_batch runs the
    InferenceSchedule.
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.micro_batches = self.gradient_accumulation_steps()
        module = self.module
        self._staged = (isinstance(module, PipelineModule)
                        and module.num_stages > 1
                        and len(jax.devices()) >= module.num_stages)
        if isinstance(module, PipelineModule) and module.num_stages > 1 \
                and not self._staged:
            logger.warning(
                f"PipelineModule wants {module.num_stages} stages but only "
                f"{len(jax.devices())} devices are visible; running "
                f"single-stage through the base engine")
        # multi-host: each process owns one physical stage and executes
        # only its own chunks; handoffs ride p2p.Channel collectives
        # (reference pipe/p2p.py:31-75). Also selectable single-process
        # via pipeline.use_p2p_channels for the driver's virtual-multichip
        # dryrun, which then exercises the multi-host code path verbatim.
        self._mh = bool(self._staged and (
            jax.process_count() > 1
            or self._config.pipe_use_p2p_channels))
        # the interpreted per-event walk is the parity oracle and the
        # bring-up executor; the compiled flat program is the default
        # (the walk re-derives every event in serialized Python)
        self._debug_schedule = bool(self._config.pipe_debug_schedule)
        self._pipe_prog = None
        self._bound_cache: Dict[Any, Any] = {}
        # telemetry: dispatch-time instrument (attached at bind time when
        # a RunMonitor is active) + cached schedule-bubble accounting
        self._pipe_instrument = None
        self._pipe_occupancy = None
        if self._staged:
            if self._mh:
                self._build_stages_mh()
            else:
                self._build_stages()

    # ------------------------------------------------------------------
    # staged construction
    # ------------------------------------------------------------------

    def _on_mesh(self, tree):
        # stage programs run on per-stage device groups and take the
        # loss scale as an operand: it stays an uncommitted array
        return tree

    def _build_stages(self):
        module: PipelineModule = self.module
        P = module.num_stages
        v = getattr(module, "interleave", 1)
        self._n_phys = P
        self._v = v
        n_mc = P * v  # model chunks; chunk index mc = chunk_id * P + stage
        self._n_mc = n_mc
        devices = jax.devices()
        G = len(devices) // P
        clip = float(self._config.gradient_clipping or 0.0)

        # tied ownership: first MODEL CHUNK containing each tied key
        self._tied_owner, self._tied_users = self._tied_maps(module, n_mc)
        tied_owner, tied_users = self._tied_owner, self._tied_users

        # whole-model params were built by the base engine; redistribute.
        # self.stages is in MODEL-CHUNK order (= model order), so every
        # walk over it — eval, checkpointing, the params property — sees
        # the layers in sequence; interleaving only changes which device
        # group hosts each chunk (chunk mc -> physical stage mc % P).
        full = jax.tree_util.tree_map(np.asarray, self._params)
        # abstract param trees: _chunk_out_avals derives every chunk's
        # output aval from these (shared with the mh build; the compiled
        # executor resolves transfer layouts from avals at bind time)
        abst = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        self._abs_layers = [abst(lp) for lp in full["layers"]]
        self._abs_tied = {k: abst(t) for k, t in full["tied"].items()}
        self._aval_cache: Dict[Any, Any] = {}
        self.stages: List[_StageRuntime] = []
        for mc in range(n_mc):
            s_phys = mc % P
            lo, hi = module.parts[mc], module.parts[mc + 1]
            rt = _StageRuntime(
                stage_id=mc,
                layers=module._layers[lo:hi],
                specs=module.layer_specs[lo:hi],
                devices=devices[s_phys * G:(s_phys + 1) * G],
                is_last=(mc == n_mc - 1),
                loss_fn=module.loss_fn,
                compute_dtype=self.compute_dtype)
            own_tied = {k: full["tied"][k] for k, o in tied_owner.items()
                        if o == mc}
            ro_tied = {k: full["tied"][k] for k, users in tied_users.items()
                       if mc in users and tied_owner[k] != mc}
            rt.own = rt.place_replicated(
                {"layers": full["layers"][lo:hi], "tied": own_tied})
            rt.ro_tied = rt.place_replicated(ro_tied)
            rt.opt_state = rt.place_replicated(
                self.optimizer.init(rt.own))
            rt.build_apply(self.optimizer, clip)
            rt.zero_acc()
            self.stages.append(rt)

        # the base engine's whole-tree placements are no longer the source
        # of truth; drop them so device memory holds one copy of the model
        self._params = None
        self._opt_state = None
        self._grad_acc = None
        log_dist(
            f"pipeline: {P} stages x {G} device(s)/stage"
            + (f" x {v} interleaved chunks" if v > 1 else "")
            + f", partitions {module.parts}, "
            f"tied={ {k: sorted(u) for k, u in tied_users.items()} }",
            ranks=[0])

    # ------------------------------------------------------------------
    # multi-host construction (one physical stage per process)
    # ------------------------------------------------------------------

    def _tied_maps(self, module, n_mc):
        def chunk_of_layer(i):
            for mc in range(n_mc):
                if module.parts[mc] <= i < module.parts[mc + 1]:
                    return mc
            return n_mc - 1

        tied_owner: Dict[str, int] = {}
        tied_users: Dict[str, set] = {}
        for i, spec in enumerate(module.layer_specs):
            if isinstance(spec, TiedLayerSpec):
                mc = chunk_of_layer(i)
                tied_owner.setdefault(spec.key, mc)
                tied_users.setdefault(spec.key, set()).add(mc)
        return tied_owner, tied_users

    def _build_stages_mh(self):
        """Per-process stage build: this process materializes ONLY its own
        model chunks; adjacent chunks on other processes are reached
        through p2p.Channel collectives. Single-process (the dryrun), all
        chunks are local and the channels are purely local collectives —
        the code path is identical.

        Deliberate duplication note: the *_mh methods mirror the
        single-controller executor with channel transfers in place of
        direct device_put reshards. The channel path functionally
        subsumes the local one, but device_put is the cheaper transport
        within one process (no collective, no zero-row add), so both are
        kept; test_pipe_multihost.py pins them to identical losses, which
        is the guard against semantic drift between the copies."""
        module: PipelineModule = self.module
        P = module.num_stages
        v = getattr(module, "interleave", 1)
        self._n_phys = P
        self._v = v
        n_mc = P * v
        self._n_mc = n_mc
        nprocs = jax.process_count()
        me = jax.process_index()
        if nprocs > 1 and P != nprocs:
            raise ValueError(
                f"multi-host pipeline runs one physical stage per process: "
                f"num_stages={P} but process_count={nprocs}")
        clip = float(self._config.gradient_clipping or 0.0)

        # device group of each physical stage: the owning process's local
        # devices multi-host; equal slices of the local devices otherwise
        groups: Dict[int, list] = {}
        if nprocs > 1:
            for d in jax.devices():
                groups.setdefault(d.process_index, []).append(d)
            for q in groups:
                groups[q] = sorted(groups[q], key=lambda d: d.id)
            sizes = {len(g) for g in groups.values()}
            if len(sizes) != 1:
                raise ValueError(
                    f"uniform devices-per-process required, got "
                    f"{ {q: len(g) for q, g in groups.items()} }")
        else:
            devs = jax.devices()
            G = len(devs) // P
            groups = {q: devs[q * G:(q + 1) * G] for q in range(P)}
        self._groups = groups

        self._tied_owner, self._tied_users = self._tied_maps(module, n_mc)

        full = jax.tree_util.tree_map(np.asarray, self._params)
        abst = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        self._abs_layers = [abst(lp) for lp in full["layers"]]
        self._abs_tied = {k: abst(t) for k, t in full["tied"].items()}

        def mine(mc):
            return nprocs == 1 or mc % P == me

        self._local: Dict[int, _StageRuntime] = {}
        for mc in range(n_mc):
            if not mine(mc):
                continue
            lo, hi = module.parts[mc], module.parts[mc + 1]
            rt = _StageRuntime(
                stage_id=mc,
                layers=module._layers[lo:hi],
                specs=module.layer_specs[lo:hi],
                devices=groups[mc % P],
                is_last=(mc == n_mc - 1),
                loss_fn=module.loss_fn,
                compute_dtype=self.compute_dtype)
            own_tied = {k: full["tied"][k]
                        for k, o in self._tied_owner.items() if o == mc}
            ro_tied = {k: full["tied"][k]
                       for k, users in self._tied_users.items()
                       if mc in users and self._tied_owner[k] != mc}
            rt.own = rt.place_replicated(
                {"layers": full["layers"][lo:hi], "tied": own_tied})
            rt.ro_tied = rt.place_replicated(ro_tied)
            rt.opt_state = rt.place_replicated(self.optimizer.init(rt.own))
            rt.build_apply(self.optimizer, clip)
            rt.zero_acc()
            self._local[mc] = rt

        self._params = None
        self._opt_state = None
        self._grad_acc = None

        # channels this process participates in (all of them when
        # single-process). Keyed by the SENDING chunk.
        def endpoint(a, b):
            return nprocs == 1 or me in (a % P, b % P)

        self._chan_act: Dict[int, Channel] = {}
        self._chan_grad: Dict[int, Channel] = {}
        for mc in range(n_mc - 1):
            if endpoint(mc, mc + 1):
                self._chan_act[mc] = Channel(groups[mc % P],
                                             groups[(mc + 1) % P])
        for mc in range(1, n_mc):
            if endpoint(mc, mc - 1):
                self._chan_grad[mc] = Channel(groups[mc % P],
                                              groups[(mc - 1) % P])
        self._chan_tied_grad: Dict[Any, Channel] = {}
        self._chan_tied_param: Dict[Any, Channel] = {}
        for key, users in self._tied_users.items():
            o = self._tied_owner[key]
            for u in sorted(users):
                if u == o or u % P == o % P:
                    continue
                if endpoint(u, o):
                    self._chan_tied_grad[(key, u)] = Channel(
                        groups[u % P], groups[o % P], replicate=True)
                    self._chan_tied_param[(key, u)] = Channel(
                        groups[o % P], groups[u % P], replicate=True)
        # checkpoint-save gather channels (tied owner -> process 0),
        # built once so periodic saves don't re-jit transfer programs.
        # Only needed multi-process (mh save is guarded on it), and an
        # existing owner->user param channel with the user on process 0
        # is reused rather than duplicated.
        self._chan_tied_save: Dict[str, Channel] = {}
        if nprocs > 1:
            for key in sorted(self._tied_owner):
                o = self._tied_owner[key]
                if o % P == 0 or not endpoint(o, 0):
                    continue
                reuse = next(
                    (self._chan_tied_param[(key, u)]
                     for u in sorted(self._tied_users[key])
                     if u % P == 0 and (key, u) in self._chan_tied_param),
                    None)
                self._chan_tied_save[key] = reuse or Channel(
                    groups[o % P], groups[0], replicate=True)
        self._gscal = GlobalScalars()
        self._aval_cache: Dict[Any, Any] = {}
        log_dist(
            f"pipeline (p2p channels): {P} stages over {nprocs} "
            f"process(es), local chunks {sorted(self._local)}, "
            f"partitions {module.parts}", ranks=[0])

    def _chunk_out_avals(self, x_aval):
        """Output aval of every model chunk, derived locally by abstract
        evaluation over the full layer stack — every process has the
        module description and the init-param shapes, so no shape
        handshake is needed (the reference sends meta tensors first,
        p2p.py:88-120)."""
        key = (tuple(x_aval.shape), str(x_aval.dtype))
        if key in self._aval_cache:
            return self._aval_cache[key]
        module: PipelineModule = self.module
        dtype = self.compute_dtype
        outs = []
        x = x_aval
        for mc in range(self._n_mc):
            lo, hi = module.parts[mc], module.parts[mc + 1]
            layers = module._layers[lo:hi]
            specs = module.layer_specs[lo:hi]

            def fwd(lparams, tied, xx, layers=layers, specs=specs):
                cast = lambda t: jax.tree_util.tree_map(
                    lambda a: a.astype(dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, t)
                lparams = cast(lparams)
                tied = cast(tied)
                for layer, spec, p in zip(layers, specs, lparams):
                    if isinstance(spec, TiedLayerSpec):
                        p = tied[spec.key]
                        if spec.forward_fn is not None:
                            xx = spec.forward_fn(layer, p, xx)
                            continue
                    xx = layer.apply(p, xx, rng=None, train=False)
                return xx

            x = jax.eval_shape(fwd, self._abs_layers[lo:hi],
                               self._abs_tied, x)
            outs.append(x)
        self._aval_cache[key] = outs
        return outs

    def _simulate_order(self, streams):
        """Canonical global event order: replay the dependency-driven
        executor symbolically. Every process derives the SAME list, so all
        processes enter their common collectives in one global total
        order — the property that makes the channel handoffs deadlock-free
        regardless of how the 1F1B streams interleave."""
        P = len(streams)
        n = self._n_mc
        sent_act = [0] * n
        sent_grad = [0] * n
        recv_act = [0] * n
        recv_grad = [0] * n
        mail_act, mail_grad = set(), set()
        events, pos = [], [0] * P

        def ready(s, tick):
            for cmd in tick:
                if isinstance(cmd, RecvActivation):
                    mc = self._mc(s, cmd)
                    if (mc, recv_act[mc]) not in mail_act:
                        return False
                if isinstance(cmd, RecvGrad):
                    mc = self._mc(s, cmd)
                    if (mc, recv_grad[mc]) not in mail_grad:
                        return False
            return True

        while True:
            progressed = False
            done = True
            for s in range(P):
                while pos[s] < len(streams[s]):
                    tick = streams[s][pos[s]]
                    if not ready(s, tick):
                        break
                    for cmd in tick:
                        mc = self._mc(s, cmd)
                        if isinstance(cmd, SendActivation):
                            mail_act.add((mc + 1, sent_act[mc]))
                            sent_act[mc] += 1
                        elif isinstance(cmd, RecvActivation):
                            recv_act[mc] += 1
                        elif isinstance(cmd, SendGrad):
                            mail_grad.add((mc - 1, sent_grad[mc]))
                            sent_grad[mc] += 1
                        elif isinstance(cmd, RecvGrad):
                            recv_grad[mc] += 1
                        events.append((s, cmd))
                    pos[s] += 1
                    progressed = True
                if pos[s] < len(streams[s]):
                    done = False
            if done:
                return events
            if not progressed:
                raise RuntimeError(
                    f"pipeline schedule deadlock in simulation at {pos}")

    def _train_batch_mh(self, data_iter):
        if self.run_monitor is not None:
            self.run_monitor.step_start(self.global_steps)
        self.tput_timer.start()
        M = self.micro_batches
        # the multi-host data contract (same as the DP engines'): every
        # process's iterator yields the identical micro-batch stream; the
        # first chunk consumes inputs, the last consumes labels
        self._mb_cache = [self._next_micro_batch_from(data_iter)
                          for _ in range(M)]
        x0 = np.asarray(self._mb_cache[0][0])
        self._aval_out = self._chunk_out_avals(
            jax.ShapeDtypeStruct(x0.shape, x0.dtype))
        n = self._n_mc
        self._mail_act = {}
        self._mail_grad = {}
        self._sent_act_cnt = [0] * n
        self._sent_grad_cnt = [0] * n
        self._recv_act_cnt = [0] * n
        self._recv_grad_cnt = [0] * n
        self._load_cnt = 0
        self._batch_key = self._next_rng()
        streams = self._pipe_streams()
        self._arm_step_guards(streams)
        for rt in self._local.values():
            rt.losses = []
            rt.fwd_count = 0
            rt.bwd_count = 0
        for s, cmd in self._simulate_order(streams):
            self._dispatch_mh(s, cmd)
        self.micro_steps += M
        self.global_samples += self.train_batch_size()
        self.tput_timer.stop(report_speed=False)
        if self.steps_per_print() and \
                self.global_steps % self.steps_per_print() == 0:
            log_dist(f"pipe step={self.global_steps} "
                     f"loss={float(self._last_loss):.4f}", ranks=[0])
        self._emit_pipe_run_event()
        return self._last_loss

    def _dispatch_mh(self, s: int, cmd):
        mc = self._mc(s, cmd)
        rt = self._local.get(mc)
        b = getattr(cmd, "buffer_id", None)
        if isinstance(cmd, LoadMicroBatch):
            mb = self._load_cnt
            self._load_cnt += 1
            if rt is not None:
                rt.x_in[b] = rt.place_batch(self._mb_cache[mb][0])
        elif isinstance(cmd, RecvActivation):
            mb = self._recv_act_cnt[mc]
            self._recv_act_cnt[mc] += 1
            if rt is not None:
                rt.x_in[b] = self._mail_act.pop((mc, mb))
        elif isinstance(cmd, ForwardPass):
            if rt is None:
                return
            mb = rt.fwd_count
            rt.fwd_count += 1
            rng = jax.random.fold_in(self._batch_key, mb * self._n_mc + mc)
            rt.rng_in[b] = rng
            if rt.is_last:
                labels = rt.place_batch(np.asarray(self._mb_cache[mb][1]))
                rt.labels[mb] = labels
                rt.y_out[b] = None
                rt.losses.append(rt.loss_j(rt.own, rt.ro_tied, rt.x_in[b],
                                           labels, rng))
            else:
                rt.y_out[b] = rt.fwd_j(rt.own, rt.ro_tied, rt.x_in[b], rng)
        elif isinstance(cmd, SendActivation):
            mb = self._sent_act_cnt[mc]
            self._sent_act_cnt[mc] += 1
            chan = self._chan_act.get(mc)
            if chan is None:
                return
            y = rt.y_out.pop(b) if rt is not None else None
            res = chan.transfer(self._aval_out[mc], y)
            if res is not None:
                self._mail_act[(mc + 1, mb)] = res
        elif isinstance(cmd, RecvGrad):
            mb = self._recv_grad_cnt[mc]
            self._recv_grad_cnt[mc] += 1
            if rt is not None:
                rt.dy_in = getattr(rt, "dy_in", {})
                rt.dy_in[b] = self._mail_grad.pop((mc, mb))
        elif isinstance(cmd, BackwardPass):
            if rt is None:
                return
            mb = rt.bwd_count
            rt.bwd_count += 1
            x = rt.x_in.pop(b)
            rng = rt.rng_in.pop(b)
            if rt.is_last:
                scale = self._scaler_state["cur_scale"]
                labels = rt.labels.pop(mb)
                dx, rt.acc, rt.acc_ro = rt.bwd_j(
                    rt.own, rt.ro_tied, x, labels, rng, scale,
                    rt.acc, rt.acc_ro)
            else:
                dy = rt.dy_in.pop(b)
                dx, rt.acc, rt.acc_ro = rt.bwd_j(
                    rt.own, rt.ro_tied, x, rng, dy, rt.acc, rt.acc_ro)
            rt.dx_out[b] = dx
        elif isinstance(cmd, SendGrad):
            mb = self._sent_grad_cnt[mc]
            self._sent_grad_cnt[mc] += 1
            chan = self._chan_grad.get(mc)
            if chan is None:
                return
            dx = rt.dx_out.pop(b) if rt is not None else None
            # dx has the aval of this chunk's INPUT = previous chunk's out
            res = chan.transfer(self._aval_out[mc - 1], dx)
            if res is not None:
                self._mail_grad[(mc - 1, mb)] = res
        elif isinstance(cmd, ReduceTiedGrads):
            self._reduce_tied_grads_mh()
        elif isinstance(cmd, ReduceGrads):
            pass  # within-stage dp reduction is implicit in the jitted loss
        elif isinstance(cmd, OptimizerStep):
            self._pipe_optimizer_step_mh()
        else:
            raise NotImplementedError(f"instruction {cmd!r}")

    def _next_micro_batch_from(self, data_iter):
        batch = next(data_iter)
        if isinstance(batch, dict):
            return batch["input_ids"], batch.get("labels")
        return batch[0], batch[1]

    def _reduce_tied_grads_mh(self):
        """Ship tied grads to the owner chunk: local pairs by direct add,
        cross-process pairs through their dedicated channel, all walked in
        the same sorted order on every process.  Runs at the LAST
        canonical ReduceTiedGrads (see _arm_step_guards)."""
        self._tied_pending -= 1
        if self._tied_pending > 0:
            return
        f32 = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), t)
        for key in sorted(self._tied_users):
            users = self._tied_users[key]
            o = self._tied_owner[key]
            ort = self._local.get(o)
            for u in sorted(users):
                if u == o:
                    continue
                if u % self._n_phys == o % self._n_phys:
                    # same process (interleave): direct add
                    if ort is not None:
                        urt = self._local[u]
                        g = jax.device_put(urt.acc_ro[key], ort.replicated)
                        ort.acc["tied"][key] = jax.tree_util.tree_map(
                            jnp.add, ort.acc["tied"][key], g)
                    continue
                chan = self._chan_tied_grad.get((key, u))
                if chan is None:
                    continue
                val = (self._local[u].acc_ro[key]
                       if chan.is_src and u in self._local else None)
                res = chan.transfer(f32(self._abs_tied[key]), val)
                if res is not None and ort is not None:
                    ort.acc["tied"][key] = jax.tree_util.tree_map(
                        jnp.add, ort.acc["tied"][key], res)

    def _pipe_optimizer_step_mh(self):
        self._step_pending -= 1
        if self._step_pending > 0:
            return
        M = self.micro_batches
        denom = jnp.asarray(self._scaler_state["cur_scale"] * M,
                            jnp.float32)
        cur_lr = self._current_lr()
        lr = None if cur_lr is None else jnp.asarray(cur_lr, jnp.float32)
        clip = float(self._config.gradient_clipping or 0.0)
        loss_sum = total_sq = ov = 0.0
        for mc in sorted(self._local):
            rt = self._local[mc]
            sq, o = rt.detect_j(rt.acc, denom)
            total_sq += float(sq)
            ov += float(np.asarray(o))
            if rt.is_last and rt.losses:
                loss_sum = float(jnp.sum(jnp.stack(rt.losses)))
        red = self._gscal.sum([loss_sum, total_sq, ov])
        loss = red[0] / M
        overflow = red[2] > 0
        clip_coef = 1.0
        if clip > 0.0:
            norm = float(np.sqrt(red[1]))
            if np.isfinite(norm) and norm > clip:
                clip_coef = clip / (norm + 1e-6)
        ovf = jnp.asarray(bool(overflow))
        for mc in sorted(self._local):
            rt = self._local[mc]
            rt.own, rt.opt_state, rt.acc = rt.apply_j(
                rt.own, rt.opt_state, rt.acc,
                lr, denom, jnp.asarray(clip_coef, jnp.float32), ovf)
            rt.acc_ro = jax.tree_util.tree_map(jnp.zeros_like, rt.acc_ro)
        self._scaler_state = self.loss_scaler.jit_update(
            self._scaler_state, jnp.asarray(bool(overflow)))
        self.global_steps += 1
        if overflow:
            self._skipped_steps += 1
            log_dist(f"pipeline overflow: skipped step, new loss scale "
                     f"{float(self._scaler_state['cur_scale'])}", ranks=[0])
        else:
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            self._refresh_tied_copies_mh()
        self._last_loss = jnp.asarray(loss, jnp.float32)
        self._emit_monitor_scalars()

    def _refresh_tied_copies_mh(self):
        for key in sorted(self._tied_users):
            users = self._tied_users[key]
            o = self._tied_owner[key]
            ort = self._local.get(o)
            for u in sorted(users):
                if u == o:
                    continue
                if u % self._n_phys == o % self._n_phys:
                    if ort is not None:
                        self._local[u].ro_tied[key] = jax.device_put(
                            ort.own["tied"][key],
                            self._local[u].replicated)
                    continue
                chan = self._chan_tied_param.get((key, u))
                if chan is None:
                    continue
                val = (ort.own["tied"][key]
                       if chan.is_src and ort is not None else None)
                res = chan.transfer(self._abs_tied[key], val)
                if res is not None and u in self._local:
                    self._local[u].ro_tied[key] = res

    # ------------------------------------------------------------------
    # schedule execution
    # ------------------------------------------------------------------

    def _mc(self, s: int, cmd) -> int:
        """Model-chunk index a command targets: interleaved instructions
        carry chunk_id (chunk c of physical stage s is model chunk
        c * n_phys + s); plain 1F1B instructions default to chunk 0."""
        return getattr(cmd, "chunk_id", 0) * self._n_phys + s

    def _pipe_streams(self):
        """Per-stage instruction streams for one train_batch — the ONE
        place both executors (and the schedule compiler) get them."""
        M = self.micro_batches
        P = self._n_phys
        if self._v > 1:
            return [list(InterleavedTrainSchedule(M, P, s, self._v).steps())
                    for s in range(P)]
        return [list(TrainSchedule(M, P, s).steps()) for s in range(P)]

    def _arm_step_guards(self, streams):
        """Per-batch countdowns for the interpreted walk: tied-grad
        reduction and the optimizer step must run at their LAST canonical
        occurrence (each stage's stream carries one of each; only at the
        last one — stage 0's, whose cooldown backward is the globally
        final backward — are every stage's gradients complete).  Acting
        at the first occurrence, as earlier rounds did, applied the
        optimizer while later events were still accumulating: those
        gradients were dropped from the step and leaked into the next
        batch's accumulators."""
        cmds = [c for st in streams for tick in st
                for c in (tick if isinstance(tick, (list, tuple))
                          else (tick,))]
        self._tied_pending = sum(isinstance(c, ReduceTiedGrads)
                                 for c in cmds)
        self._step_pending = sum(isinstance(c, OptimizerStep)
                                 for c in cmds)

    def _compiled_steps(self, x_aval):
        """Bound flat-program executor for this engine's schedule and the
        given input aval (cached — lowering runs once per engine, binding
        once per input shape)."""
        key = (tuple(x_aval.shape), str(x_aval.dtype))
        steps = self._bound_cache.get(key)
        if steps is None:
            if self._pipe_prog is None:
                events = self._simulate_order(self._pipe_streams())
                self._pipe_prog = compile_schedule(
                    events, self._mc, self._n_mc, self.micro_batches)
            if self.run_monitor is not None and \
                    self._pipe_instrument is None:
                self._pipe_instrument = PipeInstrument()
            steps = bind_program(self, self._pipe_prog,
                                 self._chunk_out_avals(x_aval),
                                 instrument=self._pipe_instrument)
            self._bound_cache[key] = steps
        return steps

    def _pipe_occupancy_stats(self):
        """Schedule-tick bubble/occupancy per physical stage (cached —
        pure function of (M, stages, interleave))."""
        if self._pipe_occupancy is None:
            self._pipe_occupancy = schedule_occupancy(self._pipe_streams())
        return self._pipe_occupancy

    def _emit_pipe_run_event(self):
        """Per-batch telemetry event for the pipeline executors: step
        bookkeeping (loss/lr/scale via the base emitter) + pipeline
        bubble accounting + measured per-op dispatch time + the comm
        counter deltas picked up by step_end."""
        rm = self.run_monitor
        if rm is None:
            return
        if rm.sync_timing and self._last_loss is not None:
            jax.block_until_ready(self._last_loss)
        pipe: Dict[str, Any] = {"occupancy": self._pipe_occupancy_stats()}
        if self._pipe_prog is not None:
            pipe["events"] = len(self._pipe_prog.events)
            pipe["source_events"] = self._pipe_prog.n_source_events
        if self._pipe_instrument is not None:
            pipe.update(self._pipe_instrument.drain())
        self._emit_run_event(pipe=pipe)

    def _train_batch_compiled(self, data_iter):
        """Default train_batch executor: an index walk over the bound
        flat program (compiler.py) — no schedule regeneration, no
        dependency re-simulation, no isinstance dispatch, no counter or
        mail-dict bookkeeping per event.  `pipeline.debug_schedule: true`
        selects the interpreted per-event oracle instead; the two are
        pinned bit-identical by tests/test_pipe_compiler.py."""
        if self.run_monitor is not None:
            self.run_monitor.step_start(self.global_steps)
        self.tput_timer.start()
        M = self.micro_batches
        self._mb_cache = [self._next_micro_batch_from(data_iter)
                          for _ in range(M)]
        x0 = np.asarray(self._mb_cache[0][0])
        steps = self._compiled_steps(
            jax.ShapeDtypeStruct(x0.shape, x0.dtype))
        self._batch_key = self._next_rng()
        # the flat program emits exactly one OP_TIED and one OP_STEP (at
        # the canonical LAST occurrence — all backwards precede them)
        self._tied_pending = 1
        self._step_pending = 1
        for rt in (self._local.values() if self._mh else self.stages):
            rt.losses = []
        for f in steps:
            f()
        if not self._mh:
            # mh sets _last_loss inside _pipe_optimizer_step_mh (global
            # reduction); single-controller averages the local losses the
            # same way the interpreted walk does
            last = self.stages[-1]
            self._last_loss = (jnp.mean(jnp.stack(last.losses))
                               if last.losses else None)
        self.micro_steps += M
        self.global_samples += self.train_batch_size()
        self.tput_timer.stop(report_speed=False)
        if self.steps_per_print() and \
                self.global_steps % self.steps_per_print() == 0:
            log_dist(f"pipe step={self.global_steps} "
                     f"loss={float(self._last_loss):.4f}", ranks=[0])
        self._emit_pipe_run_event()
        return self._last_loss

    def train_batch(self, data_iter=None):
        if not self._staged:
            return super().train_batch(data_iter)
        if data_iter is None:
            if self.training_dataloader is None:
                raise ValueError("train_batch needs data_iter or training_data")
            if not hasattr(self, "_train_iter"):
                from ..dataloader import RepeatingLoader
                self._train_iter = iter(RepeatingLoader(self.training_dataloader))
            data_iter = self._train_iter
        if not self._debug_schedule:
            return self._train_batch_compiled(data_iter)
        if self._mh:
            return self._train_batch_mh(data_iter)

        if self.run_monitor is not None:
            self.run_monitor.step_start(self.global_steps)
        self.tput_timer.start()
        M = self.micro_batches
        n_rt = len(self.stages)
        self._mail_act: Dict[Any, Any] = {}
        self._mail_grad: Dict[Any, Any] = {}
        self._data_iter = data_iter
        self._batch_key = self._next_rng()
        self._recv_act_cnt = [0] * n_rt
        self._recv_grad_cnt = [0] * n_rt
        self._sent_act_cnt = [0] * n_rt
        self._sent_grad_cnt = [0] * n_rt
        for rt in self.stages:
            rt.losses = []
            rt.fwd_count = 0
            rt.bwd_count = 0

        # the single-controller executor consumes the same canonical
        # event order the multi-host executor derives — one dependency
        # resolver for both (see _simulate_order)
        streams = self._pipe_streams()
        self._arm_step_guards(streams)
        for s, cmd in self._simulate_order(streams):
            self._dispatch_train(s, cmd)

        last = self.stages[-1]
        loss = jnp.mean(jnp.stack(last.losses)) if last.losses else None
        self.micro_steps += M
        self.global_samples += self.train_batch_size()
        self._last_loss = loss
        self.tput_timer.stop(report_speed=False)
        if self.steps_per_print() and \
                self.global_steps % self.steps_per_print() == 0:
            log_dist(f"pipe step={self.global_steps} "
                     f"loss={float(loss):.4f}", ranks=[0])
        self._emit_pipe_run_event()
        return loss

    # -- instruction handlers ------------------------------------------

    def _dispatch_train(self, s: int, cmd):
        mc = self._mc(s, cmd)
        rt = self.stages[mc]
        b = getattr(cmd, "buffer_id", None)
        if isinstance(cmd, LoadMicroBatch):
            inputs, labels = self._next_micro_batch()
            mb = rt.fwd_count
            rt.x_in[b] = rt.place_batch(inputs)
            self.stages[-1].labels[mb] = labels
        elif isinstance(cmd, RecvActivation):
            mb = self._recv_act_cnt[mc]
            self._recv_act_cnt[mc] += 1
            rt.x_in[b] = self._mail_act.pop((mc, mb))
        elif isinstance(cmd, ForwardPass):
            mb = rt.fwd_count
            rt.fwd_count += 1
            rng = jax.random.fold_in(self._batch_key,
                                     mb * len(self.stages) + mc)
            rt.rng_in[b] = rng
            if rt.is_last:
                labels = rt.place_batch(rt.labels[mb])
                rt.labels[mb] = labels
                rt.y_out[b] = None
                rt.losses.append(rt.loss_j(rt.own, rt.ro_tied, rt.x_in[b],
                                           labels, rng))
            else:
                rt.y_out[b] = rt.fwd_j(rt.own, rt.ro_tied, rt.x_in[b], rng)
        elif isinstance(cmd, SendActivation):
            # consecutive model chunks are adjacent in self.stages, so the
            # interleaved wrap (last stage chunk c -> stage 0 chunk c+1)
            # and the plain next-stage hop are both mc + 1
            nxt = self.stages[mc + 1]
            mb = self._sent_act_cnt[mc]
            self._sent_act_cnt[mc] += 1
            y = rt.y_out.pop(b)
            self._mail_act[(mc + 1, mb)] = jax.device_put(
                y, nxt.batch_sharding
                if batch_shardable(y.shape, len(nxt.devices))
                else nxt.replicated)
        elif isinstance(cmd, RecvGrad):
            mb = self._recv_grad_cnt[mc]
            self._recv_grad_cnt[mc] += 1
            rt.dy_in = getattr(rt, "dy_in", {})
            rt.dy_in[b] = self._mail_grad.pop((mc, mb))
        elif isinstance(cmd, BackwardPass):
            mb = rt.bwd_count
            rt.bwd_count += 1
            x = rt.x_in.pop(b)
            rng = rt.rng_in.pop(b)
            if rt.is_last:
                scale = self._scaler_state["cur_scale"]
                labels = rt.labels.pop(mb)
                dx, rt.acc, rt.acc_ro = rt.bwd_j(
                    rt.own, rt.ro_tied, x, labels, rng, scale,
                    rt.acc, rt.acc_ro)
            else:
                dy = rt.dy_in.pop(b)
                dx, rt.acc, rt.acc_ro = rt.bwd_j(
                    rt.own, rt.ro_tied, x, rng, dy, rt.acc, rt.acc_ro)
            rt.dx_out[b] = dx
        elif isinstance(cmd, SendGrad):
            prev = self.stages[mc - 1]
            mb = self._sent_grad_cnt[mc]
            self._sent_grad_cnt[mc] += 1
            dx = rt.dx_out.pop(b)
            self._mail_grad[(mc - 1, mb)] = jax.device_put(
                dx, prev.batch_sharding
                if batch_shardable(dx.shape, len(prev.devices))
                else prev.replicated)
        elif isinstance(cmd, ReduceTiedGrads):
            self._reduce_tied_grads()
        elif isinstance(cmd, ReduceGrads):
            pass  # within-stage dp reduction is implicit in the jitted loss
        elif isinstance(cmd, OptimizerStep):
            self._pipe_optimizer_step()
        else:
            raise NotImplementedError(f"instruction {cmd!r}")

    def _next_micro_batch(self):
        return self._next_micro_batch_from(self._data_iter)

    def _reduce_tied_grads(self):
        """Ship non-owner tied grads to the owner stage and sum (the
        single-controller form of reference pipe/engine.py's
        _all_reduce_tied_weight_gradients).  Runs at the LAST canonical
        ReduceTiedGrads (see _arm_step_guards)."""
        self._tied_pending -= 1
        if self._tied_pending > 0:
            return
        for key, users in self._tied_users.items():
            owner = self.stages[self._tied_owner[key]]
            total = owner.acc["tied"][key]
            for s in sorted(users):
                rt = self.stages[s]
                if rt.stage_id == owner.stage_id:
                    continue
                g = jax.device_put(rt.acc_ro[key], owner.replicated)
                total = jax.tree_util.tree_map(jnp.add, total, g)
            owner.acc["tied"][key] = total

    def _pipe_optimizer_step(self):
        self._step_pending -= 1
        if self._step_pending > 0:
            return
        denom = jnp.asarray(
            self._scaler_state["cur_scale"] * self.micro_batches,
            jnp.float32)
        cur_lr = self._current_lr()
        lr = None if cur_lr is None else jnp.asarray(cur_lr, jnp.float32)
        clip = float(self._config.gradient_clipping or 0.0)
        # detect BEFORE apply: global norm for clipping + global overflow,
        # so every stage applies (or skips) the step together (reference
        # pipe engine all-reduces both over pipeline ranks)
        detects = [rt.detect_j(rt.acc, denom) for rt in self.stages]
        total_sq = sum(float(sq) for sq, _ in detects)
        overflow = bool(np.any([np.asarray(ov) for _, ov in detects]))
        clip_coef = 1.0
        if clip > 0.0:
            norm = float(np.sqrt(total_sq))
            if np.isfinite(norm) and norm > clip:
                clip_coef = clip / (norm + 1e-6)
        ovf = jnp.asarray(overflow)
        for rt in self.stages:
            rt.own, rt.opt_state, rt.acc = rt.apply_j(
                rt.own, rt.opt_state, rt.acc,
                lr, denom, jnp.asarray(clip_coef, jnp.float32), ovf)
            rt.acc_ro = jax.tree_util.tree_map(
                jnp.zeros_like, rt.acc_ro)
        self._scaler_state = self.loss_scaler.jit_update(
            self._scaler_state, jnp.asarray(overflow))
        self.global_steps += 1
        if overflow:
            # all stages selected their old params in-jit; undo bookkeeping
            self._skipped_steps += 1
            log_dist(f"pipeline overflow: skipped step, new loss scale "
                     f"{float(self._scaler_state['cur_scale'])}", ranks=[0])
        else:
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            self._refresh_tied_copies()
        self._emit_monitor_scalars()

    def _refresh_tied_copies(self):
        for key, users in self._tied_users.items():
            owner = self.stages[self._tied_owner[key]]
            for s in sorted(users):
                rt = self.stages[s]
                if rt.stage_id == owner.stage_id:
                    continue
                rt.ro_tied[key] = jax.device_put(
                    owner.own["tied"][key], rt.replicated)

    @property
    def params(self):
        """Full {'layers': ..., 'tied': ...} pytree reassembled from the
        per-stage placements (the base property would return the nulled
        whole-tree placement in staged mode — exports/params access must
        see the live stage weights)."""
        if not self._staged:
            return DeepSpeedEngine.params.fget(self)
        module: PipelineModule = self.module
        layers = [None] * module.num_layers()
        tied = {}
        if self._mh:
            # process-local view: layers this process does not own stay
            # None (multi-host processes cannot address remote params)
            for mc, rt in self._local.items():
                lo = module.parts[mc]
                for j, lp in enumerate(rt.own["layers"]):
                    layers[lo + j] = lp
                tied.update(rt.own["tied"])
            return {"layers": layers, "tied": tied}
        for s, rt in enumerate(self.stages):
            lo = module.parts[s]
            for j, lp in enumerate(rt.own["layers"]):
                layers[lo + j] = lp
            tied.update(rt.own["tied"])
        return {"layers": layers, "tied": tied}

    # ------------------------------------------------------------------
    # multi-host checkpointing: reference-layout per-layer files, one
    # writer per owned piece (the sharded-checkpoint rule, engine.py
    # one-writer-per-piece), reassembled into the SAME on-disk format the
    # single-process engine writes, so checkpoints are portable between
    # multi-host and single-host runs
    # ------------------------------------------------------------------

    def _mh_write(self, path, payload):
        from flax import serialization

        # write-tmp + fsync + rename: the pre-`latest` barrier only orders
        # processes, not the page cache — a host crash after the barrier
        # must not leave `latest` pointing at torn chunk files
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(serialization.msgpack_serialize(
                jax.tree_util.tree_map(np.asarray, payload)))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        # the containing dir is fsynced ONCE per save (before the
        # pre-`latest` barrier), not here — one barrier, not one per file

    def _mh_read(self, path):
        from flax import serialization

        with open(path, "rb") as f:
            return serialization.msgpack_restore(f.read())

    def _chunk_optim_name(self, ckpt_dir, mc):
        return os.path.join(ckpt_dir, f"pipe_optim_chunk{mc:02d}.msgpack")

    def _read_local_chunks(self, ckpt_dir, tied, load_optimizer_states):
        """Read every local chunk's layer files, owned tied params AND
        optimizer chunk states in one pass BEFORE mutating any runtime
        state, so any unreadable file leaves the engine untouched."""
        module: PipelineModule = self.module
        staged = {}
        single_optim = None  # single-host-written optimizer fallback
        for mc in sorted(self._local):
            lo, hi = module.parts[mc], module.parts[mc + 1]
            layers = [jax.tree_util.tree_map(
                jnp.asarray,
                self._mh_read(ckpt_io.layer_ckpt_name(ckpt_dir, i)))
                for i in range(lo, hi)]
            own_tied = {k: jax.tree_util.tree_map(jnp.asarray, tied[k])
                        for k, o in self._tied_owner.items() if o == mc}
            restored = None
            if load_optimizer_states:
                cpath = self._chunk_optim_name(ckpt_dir, mc)
                if os.path.isfile(cpath):
                    restored = self._mh_read(cpath)
                else:  # single-host-written checkpoint: list layout
                    if single_optim is None:
                        opath = ckpt_io.optim_ckpt_name(ckpt_dir)
                        if os.path.isfile(opath):
                            so = self._mh_read(opath)
                            if isinstance(so, dict) and \
                                    so.get("__dstpu_ckpt_v2__"):
                                # v2 wrapper: payload under "state",
                                # sharded leaves in rank piece files
                                pieces = ckpt_io._load_rank_pieces(
                                    ckpt_dir, 0)
                                so = so.get("state")
                                if pieces:
                                    so = ckpt_io._reassemble(so, pieces)
                            single_optim = so or {}
                    if single_optim and single_optim.get(
                            "pipeline_parts") == list(module.parts):
                        restored = single_optim["optimizer_state"][mc]
                if restored is None:
                    # loud, not silent: resuming with fresh Adam moments
                    # is a numerics regression the caller must know about
                    logger.warning(
                        f"load_checkpoint: no optimizer state for model "
                        f"chunk {mc} in {ckpt_dir}; its optimizer "
                        f"re-initializes from scratch")
            staged[mc] = (layers, own_tied, restored)
        return staged

    def _save_checkpoint_mh(self, save_dir, tag=None, client_state=None,
                            save_latest=True):
        if tag is None:
            tag = f"global_step{self.global_steps}"
        module: PipelineModule = self.module
        me = jax.process_index()
        ckpt_dir = os.path.join(save_dir, str(tag))
        os.makedirs(ckpt_dir, exist_ok=True)

        for mc in sorted(self._local):
            rt = self._local[mc]
            lo = module.parts[mc]
            # layers only: tied params are gathered separately below, so
            # a whole-tree D2H would copy the (large) tied tables twice
            layers_np = jax.tree_util.tree_map(np.asarray,
                                               rt.own["layers"])
            for j, lp in enumerate(layers_np):
                self._mh_write(ckpt_io.layer_ckpt_name(ckpt_dir, lo + j),
                               lp)
            state = rt.opt_state
            if hasattr(self.optimizer, "serialize_state"):
                state = self.optimizer.serialize_state(state)
            self._mh_write(self._chunk_optim_name(ckpt_dir, mc), state)

        # tied params: ship each owner's copy to process 0 so the module
        # skeleton carries the full tied dict (single-host-loadable);
        # every process constructs/enters the channels in sorted order
        tied_full = {}
        for key in sorted(self._tied_owner):
            o = self._tied_owner[key]
            if o % self._n_phys == 0:
                if me == 0:
                    tied_full[key] = jax.tree_util.tree_map(
                        np.asarray, self._local[o].own["tied"][key])
                continue
            chan = self._chan_tied_save.get(key)
            if chan is not None:
                val = (self._local[o].own["tied"][key]
                       if o in self._local else None)
                res = chan.transfer(self._abs_tied[key], val)
                if me == 0:
                    tied_full[key] = jax.tree_util.tree_map(np.asarray,
                                                            res)

        if me == 0:
            L = module.num_layers()
            model_state = {
                "module": {"layers": [None] * L, "tied": tied_full,
                           "num_layers": L},
                "lr_scheduler": (self.lr_scheduler.state_dict()
                                 if self.lr_scheduler is not None else None),
                "loss_scaler": {k: np.asarray(v)
                                for k, v in self._scaler_state.items()},
                "rng_key": np.asarray(self._rng_key),
                "pipeline_parts": list(module.parts),
                **self._client_state(client_state),
            }
            self._mh_write(ckpt_io.model_ckpt_name(ckpt_dir), model_state)
        # make this process's renames durable (single directory barrier
        # for all files written above) AND the <tag> dirent itself (lives
        # in save_dir — per-host filesystems each need it), then the
        # collective barrier: every process's files are on disk before
        # rank 0 publishes `latest`
        _fsync_dir(ckpt_dir)
        _fsync_dir(save_dir)
        self._gscal.sum(np.zeros(1, np.float32))
        if me == 0:
            # the collective barrier above IS this writer's commit
            # rendezvous: every process's files are durable, so publish
            # the commit marker (keeps mh tags first-class for
            # read_latest_tag's committed-tag resolution — a marker-less
            # tag in a marker-bearing dir would be skipped as torn)
            ckpt_io.write_commit_marker(
                save_dir, tag,
                meta={"world_size": jax.process_count(),
                      "pipeline_parts": list(module.parts),
                      "zero_stage": self.zero_optimization_stage()},
                world_size=jax.process_count())
        if save_latest and me == 0:
            # atomic publish: write-tmp-then-rename so a crash mid-write
            # can't leave a truncated `latest`
            latest = os.path.join(save_dir, "latest")
            tmp = latest + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(tag))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, latest)
            _fsync_dir(save_dir)
        log_dist(f"saved multi-host pipeline checkpoint {tag} to "
                 f"{ckpt_dir}", ranks=[0])
        return True

    def _load_checkpoint_mh(self, load_dir, tag=None,
                            load_optimizer_states=True,
                            load_lr_scheduler_states=True):
        module: PipelineModule = self.module
        if tag is None:
            tag = ckpt_io.read_latest_tag(load_dir)
            if tag is None:
                logger.warning(f"load_checkpoint: no latest in {load_dir}")
                return None, {}
        ckpt_dir = os.path.join(load_dir, str(tag))
        mpath = ckpt_io.model_ckpt_name(ckpt_dir)
        if not os.path.isfile(mpath):
            logger.warning(f"load_checkpoint: {mpath} not found")
            return None, {}
        model_state = self._mh_read(mpath)
        tied = (model_state.get("module") or {}).get("tied", {})
        if model_state.get("pipeline_parts") not in (None,
                                                     list(module.parts)):
            raise ValueError(
                f"checkpoint pipeline_parts "
                f"{model_state.get('pipeline_parts')} != current "
                f"{list(module.parts)}; repartitioned multi-host reload "
                f"is unsupported")
        try:
            staged = self._read_local_chunks(ckpt_dir, tied,
                                             load_optimizer_states)
        except Exception as e:
            # partial/torn checkpoint (a writer died before the barrier:
            # missing files raise FileNotFoundError, truncated msgpack
            # raises unpack errors) or layer/tied mismatch — keep the
            # warn-and-return contract, don't crash training scripts;
            # NOTHING was mutated (the staging pass reads everything
            # before the loop below touches runtime state)
            logger.warning(f"load_checkpoint: unreadable/incomplete "
                           f"checkpoint in {ckpt_dir}: {e!r}")
            return None, {}
        for mc in sorted(self._local):
            rt = self._local[mc]
            layers, own_tied, restored = staged[mc]
            rt.own = rt.place_replicated({"layers": layers,
                                          "tied": own_tied})
            if restored is not None:
                if hasattr(self.optimizer, "deserialize_state"):
                    restored = self.optimizer.deserialize_state(
                        restored, rt.own)
                rt.opt_state = rt.place_replicated(
                    jax.tree_util.tree_map(jnp.asarray, restored))
            rt.zero_acc()
        self._refresh_tied_copies_mh()
        return self._finish_pipe_load(model_state, ckpt_dir,
                                      load_lr_scheduler_states)

    def _finish_pipe_load(self, model_state, ckpt_dir,
                          load_lr_scheduler_states):
        """Shared tail of both pipeline loaders: scaler/scheduler/rng/
        counter restore + client-state extraction (one copy, no drift)."""
        if model_state.get("loss_scaler") is not None:
            self._scaler_state = {k: jnp.asarray(v) for k, v in
                                  model_state["loss_scaler"].items()}
        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                model_state.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(model_state["lr_scheduler"])
        if model_state.get("rng_key") is not None:
            self._rng_key = jnp.asarray(model_state["rng_key"])
        self.global_steps = int(model_state.get("global_steps", 0))
        self.global_samples = int(model_state.get("global_samples", 0))
        self.micro_steps = int(model_state.get("micro_steps", 0))
        self.loaded_checkpoint_tag = os.path.basename(ckpt_dir)
        client_state = {k: v for k, v in model_state.items()
                        if k not in ("module", "lr_scheduler",
                                     "loss_scaler", "pipeline_parts")}
        return ckpt_dir, client_state

    def _runtimes(self) -> List[_StageRuntime]:
        """Stage runtimes in model-chunk order. In channel (mh) mode this
        is only valid when every chunk is local (single process)."""
        if not self._mh:
            return self.stages
        return [self._local[mc] for mc in sorted(self._local)]

    def memory_status(self, tag: str = ""):
        """Per-stage device-memory report (reference pipe/engine.py:
        1195-1243 memory_status): bytes in use / peak per stage's device
        group, plus live pipeline-buffer counts."""
        if not self._staged:
            from ...utils.timer import SynchronizedWallClockTimer

            log_dist(f"MEMSTATS {tag} "
                     f"{SynchronizedWallClockTimer.memory_usage()}",
                     ranks=[0])
            return
        for rt in (self._local.values() if self._mh else self.stages):
            used = peak = 0
            for d in rt.devices:
                stats = (d.memory_stats() or {}) \
                    if hasattr(d, "memory_stats") else {}
                used += stats.get("bytes_in_use", 0) or 0
                peak += stats.get("peak_bytes_in_use", 0) or 0
            log_dist(
                f"MEMSTATS {tag} stage {rt.stage_id}: "
                f"in_use {used / 2**30:.2f} GB | peak {peak / 2**30:.2f} GB"
                f" | buffers: x_in={len(rt.x_in)} y_out={len(rt.y_out)} "
                f"dx_out={len(rt.dx_out)}", ranks=[0])

    # ------------------------------------------------------------------
    # eval / inference
    # ------------------------------------------------------------------

    def eval_batch(self, data_iter):
        if not self._staged:
            batch = next(data_iter) if hasattr(data_iter, "__next__") \
                else data_iter
            return super().eval_batch(batch)
        if not hasattr(data_iter, "__next__"):
            data_iter = iter([data_iter])
        if self._mh:
            return self._eval_batch_mh(data_iter)
        self._mail_act = {}
        self._mail_grad = {}
        self._data_iter = data_iter
        self._batch_key = self._next_rng()
        M = self.micro_batches
        P = len(self.stages)
        for rt in self.stages:
            rt.losses = []
            rt.fwd_count = 0
        # forward-only streams; consume as many micro batches as available
        losses = []
        for mb in range(M):
            try:
                inputs, labels = self._next_micro_batch()
            except StopIteration:
                break
            x = self.stages[0].place_batch(inputs)
            for rt in self.stages[:-1]:
                x = rt.fwd_eval_j(rt.own, rt.ro_tied, x, None)
                nxt = self.stages[rt.stage_id + 1]
                x = jax.device_put(
                    x, nxt.batch_sharding
                    if batch_shardable(x.shape, len(nxt.devices))
                    else nxt.replicated)
            last = self.stages[-1]
            losses.append(last.eval_loss_j(
                last.own, last.ro_tied, x, last.place_batch(labels), None))
        return jnp.mean(jnp.stack(losses)) if losses else None

    def _eval_batch_mh(self, data_iter):
        """Forward-only walk in model-chunk order; every process enters
        the activation channels in the same (mc, mb) order, the loss is
        summed globally at the end."""
        M = self.micro_batches
        loss_sum = 0.0
        count = 0
        for _ in range(M):
            try:
                inputs, labels = self._next_micro_batch_from(data_iter)
                got = 1.0
            except StopIteration:
                got = 0.0
            # Contract check BEFORE the chunk walk: every process must see
            # the identical data stream.  If iterators diverge, the process
            # that got data would enter channel collectives its peer never
            # joins and the job would hang — sum a got-data flag and raise
            # on mismatch instead (cheap: one tiny collective per mb).
            total_got = float(self._gscal.sum([got])[0])
            if total_got == 0.0:
                break
            if total_got != float(self._gscal.nprocs):
                raise RuntimeError(
                    f"eval data iterators diverged across processes: "
                    f"{int(total_got)}/{self._gscal.nprocs} processes had a "
                    f"micro batch at index {count} — every process must be "
                    f"given an identical data stream")
            count += 1
            avals = self._chunk_out_avals(jax.ShapeDtypeStruct(
                np.asarray(inputs).shape, np.asarray(inputs).dtype))
            x = None
            first = self._local.get(0)
            if first is not None:
                x = first.place_batch(inputs)
            for mc in range(self._n_mc):
                rt = self._local.get(mc)
                if rt is not None:
                    if rt.is_last:
                        loss_sum += float(rt.eval_loss_j(
                            rt.own, rt.ro_tied, x,
                            rt.place_batch(np.asarray(labels)), None))
                        continue
                    x = rt.fwd_eval_j(rt.own, rt.ro_tied, x, None)
                if mc < self._n_mc - 1:
                    chan = self._chan_act.get(mc)
                    if chan is not None:
                        res = chan.transfer(
                            avals[mc], x if rt is not None else None)
                        if res is not None:
                            x = res
        red = self._gscal.sum([loss_sum])
        return (jnp.asarray(red[0] / count, jnp.float32)
                if count else None)

    def inference_batch(self, data_iter):
        """One-shot forward over the pipeline stages (EleutherAI
        addition, reference pipe/engine.py:422).

        This is the reference-era SINGLE-BATCH path: one fixed batch,
        full forward, no KV cache, no admission — every token of every
        sequence recomputes the whole prefix.  For actual serving
        (autoregressive decode, continuous batching, paged KV,
        latency/throughput accounting) use `deepspeed_tpu.serving`
        (docs/tutorials/serving.md): `ServeEngine.submit()` /
        `generate()` is the supported inference path, pinned
        token-identical to `models/generation.generate`.  This method
        stays for batch-scoring workloads (perplexity eval over a
        fixed set) where recompute is acceptable and the pipeline
        stages are already resident — the two paths must not silently
        diverge, hence the one-time pointer logged below."""
        from ...utils.logging import warning_once

        warning_once(
            "pipe.engine.inference_batch is the reference-era one-shot "
            "forward (full prefix recompute, no batching across "
            "requests); for serving use deepspeed_tpu.serving "
            "(ServeEngine — continuous batching over a paged KV cache, "
            "docs/tutorials/serving.md)")
        batch = next(data_iter) if hasattr(data_iter, "__next__") else data_iter
        inputs = batch[0] if isinstance(batch, (tuple, list)) else batch
        if not self._staged:
            return self.module.apply(self._params, inputs, train=False)
        if self._mh:
            avals = self._chunk_out_avals(jax.ShapeDtypeStruct(
                np.asarray(inputs).shape, np.asarray(inputs).dtype))
            x = None
            if 0 in self._local:
                x = self._local[0].place_batch(inputs)
            for mc in range(self._n_mc):
                rt = self._local.get(mc)
                if rt is not None:
                    x = rt.fwd_eval_j(rt.own, rt.ro_tied, x, None)
                if mc < self._n_mc - 1:
                    chan = self._chan_act.get(mc)
                    if chan is not None:
                        res = chan.transfer(
                            avals[mc], x if rt is not None else None)
                        if res is not None:
                            x = res
            # the final output lives on the last chunk's owner; other
            # processes return None (the reference's last-rank-only output)
            return x if (self._n_mc - 1) in self._local else None
        x = self.stages[0].place_batch(inputs)
        for rt in self.stages:
            x = rt.fwd_eval_j(rt.own, rt.ro_tied, rt.place_batch(x), None)
        return x

    # ------------------------------------------------------------------
    # checkpointing: per-layer files (reference pipe/module.py:520-578)
    # ------------------------------------------------------------------

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        if not self._staged:
            return super().save_checkpoint(save_dir, tag, client_state,
                                           save_latest)
        if self._mh and jax.process_count() > 1:
            return self._save_checkpoint_mh(save_dir, tag, client_state,
                                            save_latest)
        if tag is None:
            tag = f"global_step{self.global_steps}"
        module: PipelineModule = self.module
        layer_states = {}
        tied_states = {}
        for s, rt in enumerate(self._runtimes()):
            lo = module.parts[s]
            own_np = jax.tree_util.tree_map(np.asarray, rt.own)
            for j, lp in enumerate(own_np["layers"]):
                layer_states[lo + j] = lp
            tied_states.update(own_np["tied"])
        model_state = {
            "module": {"layers": [layer_states.get(i)
                                  for i in range(module.num_layers())],
                       "tied": tied_states},
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if self.lr_scheduler is not None else None),
            "loss_scaler": {k: np.asarray(v)
                            for k, v in self._scaler_state.items()},
            "rng_key": np.asarray(self._rng_key),
            **self._client_state(client_state),
        }
        def pack_opt(rt):
            state = rt.opt_state
            if hasattr(self.optimizer, "serialize_state"):
                # namedtuple optimizer states (optax) can't ride msgpack
                state = self.optimizer.serialize_state(state)
            return jax.tree_util.tree_map(np.asarray, state)

        optim_state = {
            "optimizer_state": [pack_opt(rt) for rt in self._runtimes()],
            "pipeline_parts": list(module.parts),
            "zero_stage": self.zero_optimization_stage(),
            "offload": False,
        }
        ckpt_io.save_checkpoint_state(
            save_dir, tag, model_state, optim_state, save_latest=save_latest,
            layer_states=layer_states, tied_states=tied_states)
        return True

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True):
        if not self._staged:
            return super().load_checkpoint(load_dir, tag, load_module_strict,
                                           load_optimizer_states,
                                           load_lr_scheduler_states)
        if self._mh and jax.process_count() > 1:
            return self._load_checkpoint_mh(load_dir, tag,
                                            load_optimizer_states,
                                            load_lr_scheduler_states)
        try:
            ckpt_dir, model_state, optim_state = \
                ckpt_io.load_checkpoint_state(load_dir, tag)
        except FileNotFoundError as e:
            logger.warning(f"load_checkpoint: {e}")
            return None, {}
        module: PipelineModule = self.module
        if optim_state is None:
            # multi-host-written checkpoint: per-chunk optim files instead
            # of the single zero_pp_rank file — reassemble the list layout
            chunk_files = [self._chunk_optim_name(ckpt_dir, mc)
                           for mc in range(len(module.parts) - 1)]
            if all(os.path.isfile(p) for p in chunk_files):
                optim_state = {
                    "optimizer_state": [self._mh_read(p)
                                        for p in chunk_files],
                    "pipeline_parts": model_state.get(
                        "pipeline_parts", list(module.parts)),
                }
        layers = model_state["module"]["layers"]
        tied = model_state["module"]["tied"]
        for s, rt in enumerate(self._runtimes()):
            lo, hi = module.parts[s], module.parts[s + 1]
            own_tied = {k: tied[k] for k, o in self._tied_owner.items()
                        if o == s}
            rt.own = rt.place_replicated(
                {"layers": [jax.tree_util.tree_map(jnp.asarray, l)
                            for l in layers[lo:hi]],
                 "tied": own_tied})
            if load_optimizer_states and optim_state is not None and \
                    optim_state.get("pipeline_parts") == list(module.parts):
                restored = optim_state["optimizer_state"][s]
                if hasattr(self.optimizer, "deserialize_state"):
                    restored = self.optimizer.deserialize_state(
                        restored, rt.own)
                rt.opt_state = rt.place_replicated(
                    jax.tree_util.tree_map(jnp.asarray, restored))
            rt.zero_acc()
        if self._mh:
            self._refresh_tied_copies_mh()
        else:
            self._refresh_tied_copies()
        return self._finish_pipe_load(model_state, ckpt_dir,
                                      load_lr_scheduler_states)
