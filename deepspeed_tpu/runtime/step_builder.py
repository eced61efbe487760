"""Schedule-driven step builder: ONE composition engine for every
jitted training-step program.

The engine used to hand-build three step paths (the fused gas==1
program, the full_scan global-batch program, and the split micro/apply
pair) with triplicated prep/grad/reduce/apply bodies.  This module
rebuilds them from four shared stage closures composed per a declarative
`StepSchedule`:

  prep    master params -> compute params (dtype cast, qwZ gather)
  grad    compute params + micro batch -> local or reduced gradients
  reduce  the DP gradient wire: in-program collectives (serial), or the
          encode half of the host-exchanged overlap wire
  apply   unscale, overflow check, clip, optimizer, ZeRO constraints,
          loss-scale update

Schedules:

  fused   gas==1: prep+grad+reduce+apply as ONE program
  scan    gas>1:  prep + lax.scan(grad+reduce) + apply as ONE program
  split   per-micro grad+reduce programs + an apply program (offload,
          manual forward/backward driving, heterogeneous batches)
  onebit  the compressed-wire fused step (engine._build_onebit_step)

  overlap (comm.overlap, stage<3 bucketed wire): per-micro GRADS
          programs emit encoded wire payloads, the host exchange
          (runtime/comm/overlap.py) moves them while the device runs
          the next micro's program, COMBINE programs reduce with
          bit-identical math, and the apply program is the serial one.
          With ZeRO-3 + quantized_weights the same exchange instead
          carries the qwZ parameter gather (prefetched right behind
          the previous step's apply), and the serial schedules run with
          an EXTERNAL prep: the gathered compute params arrive as a
          program argument.

Per-dispatch wire/qwZ counter accounting lives here too (CountedFn):
each emitted program knows how many gradient-wire reductions and qwZ
gathers one dispatch performs, so the byte math is written once and
holds on every schedule — including overlap, where the same plan bytes
ride the host exchange instead of an XLA collective.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..monitor.counters import COUNTERS
from ..monitor.tracing import phase
from ..utils.logging import log_dist
from .utils import clip_grad_norm, has_overflow


class StepLR(NamedTuple):
    """The learning rate of a step dispatched while the step before it
    may still be running.  An overflow in that step rolls the scheduler
    one index back, so the host cannot know which of two rates this
    step needs until the flag arrives — and waiting for it would drain
    the device between steps.  The program is handed both rates and
    the flag as the device value it already is, and selects, as the
    update itself does on its own step's flag."""

    rates: jax.Array          # f32[2]: previous step applied, skipped
    prev_overflow: jax.Array  # bool[]; a constant false once settled


def select_lr(lr):
    """A `StepLR` resolved in the program; a plain scalar or None
    (the optimizer's own default) passes through."""
    if isinstance(lr, StepLR):
        return jnp.where(lr.prev_overflow, lr.rates[1], lr.rates[0])
    return lr


class StepSchedule(NamedTuple):
    """The declarative plan `StepBuilder.build` composes programs from."""

    composition: str        # "fused" | "scan" | "split" | "onebit"
    overlap_wire: bool      # grads/exchange/combine pipeline for the
    #                         bucketed gradient wire
    overlap_qwz: bool       # prep is external: the qwZ gather rides the
    #                         host exchange, prefetched across steps
    gas: int

    def describe(self) -> str:
        parts = [f"composition={self.composition}", f"gas={self.gas}"]
        if self.overlap_wire:
            parts.append("gradient wire host-exchanged (overlap)")
        if self.overlap_qwz:
            parts.append("qwZ gather host-exchanged (prefetch)")
        return "StepSchedule: " + ", ".join(parts)


class CountedFn:
    """A jitted step program plus its per-dispatch counter accounting:
    calling it records exactly the wire/qwZ bytes one dispatch moves
    (the engine's monitor picks the deltas up per step).  `.fn` is the
    raw jitted callable for AOT analysis (flops profiling) — analysis
    traces must not bump dispatch counters (and must not land trace
    spans either, for the same reason).

    `trace`: an optional zero-arg callable returning (recorder, step)
    when the in-flight step is sampled, else None — each dispatch then
    lands as a `dispatch.<name>` span on the trace timeline.  Every
    dispatch is a `train.launch` phase (`monitor.tracing.phase`), so a
    profiler capture shows it beside the device's operations.  Dispatch
    wall only (programs run async): the span bounds the host-side
    enqueue, not device execution."""

    __slots__ = ("fn", "_account", "_trace", "_name")

    def __init__(self, fn, account=None, trace=None, name=None):
        self.fn = fn
        self._account = account
        self._trace = trace
        self._name = name

    def __call__(self, *args):
        if self._account is not None:
            self._account()
        tr = self._trace() if self._trace is not None else None
        with (phase("train.launch") if tr is None else
              phase("train.launch", tr[0], "train",
                    span=f"dispatch.{self._name}", step=tr[1])):
            return self.fn(*args)


class StepBuilder:
    """Builds the engine's `_step_fns` dict from the current config,
    bucket plan, qwZ gather and overlap mode."""

    def __init__(self, engine):
        self.engine = engine

    # -- per-dispatch counter accounting (ONE home for all paths) -----

    def _account_wire(self, plan, events: int):
        """The plan's predicted per-reduction payload, recorded as the
        step executes (unlike the traced-occurrence `bucket.*`
        counters).  Identical math on every schedule: under overlap the
        same bytes ride the host exchange instead of an XLA
        collective."""
        if plan is None:
            return
        COUNTERS.add("grad_wire.reduce",
                     plan.wire_bytes_per_reduction * events,
                     calls=plan.collectives_per_reduction * events)
        COUNTERS.add("grad_wire.reduce_logical",
                     plan.wire_bytes_logical_per_reduction * events,
                     calls=plan.collectives_per_reduction * events)
        if plan.hierarchical:
            for name, nbytes, calls in (
                    ("intra", plan.wire_bytes_intra_per_reduction,
                     plan.collectives_intra_per_reduction),
                    ("intra_logical",
                     plan.wire_bytes_intra_logical_per_reduction,
                     plan.collectives_intra_per_reduction),
                    ("inter", plan.wire_bytes_inter_per_reduction,
                     plan.collectives_inter_per_reduction),
                    ("inter_logical",
                     plan.wire_bytes_inter_logical_per_reduction,
                     plan.collectives_inter_per_reduction)):
                COUNTERS.add(f"grad_wire.{name}", nbytes * events,
                             calls=calls * events)

    def _account_qwz(self, gather, events: int):
        if gather is None:
            return
        COUNTERS.add("qwz.gather",
                     gather.wire_bytes_per_gather * events,
                     calls=gather.collectives_per_gather * events)

    def _counted(self, fn, plan=None, wire_events=0, qwz=None,
                 qwz_events=0, name=None):
        eng = self.engine
        trace = None
        if name is not None:
            # Step fns are built before _init_run_monitor attaches the
            # tracer, so the gate has to live inside the closure.
            def trace():
                tr = getattr(eng, "_dispatch_tracer", None)
                tr = tr() if tr is not None else None
                return None if tr is None else (tr, eng.global_steps + 1)
        if not wire_events and not qwz_events:
            return CountedFn(fn, trace=trace, name=name)
        account = lambda: (self._account_wire(plan, wire_events),
                           self._account_qwz(qwz, qwz_events))
        return CountedFn(fn, account, trace=trace, name=name)

    # -- schedule resolution ------------------------------------------

    def plan_schedule(self) -> StepSchedule:
        eng = self.engine
        gas = eng.gradient_accumulation_steps()
        overlap_wire = (eng._overlap_mode == "wire"
                        and eng.bucket_plan is not None
                        and eng._capture_layers is None)
        overlap_qwz = (eng._overlap_mode == "qwz"
                       and eng._qwz_gather is not None)
        if eng._use_onebit_comm():
            comp = "onebit"
        elif overlap_wire:
            comp = "split"  # per-micro grads dispatches ARE the overlap
        elif gas == 1 and eng._offload is None:
            comp = "fused"
        elif gas > 1 and eng._offload is None:
            comp = "scan"
        else:
            comp = "split"
        return StepSchedule(comp, overlap_wire, overlap_qwz, gas)

    # -- program construction -----------------------------------------

    def build(self) -> dict:
        eng = self.engine
        schedule = self.plan_schedule()
        model = eng.module
        compute_dtype = eng.compute_dtype
        plan = eng.zero_plan
        opt = eng.optimizer
        gas = schedule.gas
        clip = float(eng._config.gradient_clipping or 0.0)
        prescale = eng._config.prescale_gradients
        predivide = float(eng._config.gradient_predivide_factor or 1.0)
        scaler = eng.loss_scaler
        pld_enabled = eng.progressive_layer_drop is not None
        capture = eng._capture_layers
        store_grads = eng._store_gradients
        mesh_info = eng.mesh_info

        def cast(tree, dtype):
            return jax.tree_util.tree_map(
                lambda x: x.astype(dtype) if jnp.issubdtype(
                    x.dtype, jnp.floating) else x, tree)

        qwz = eng._qwz_gather

        # -- prep stage: master params -> the compute-side replica ----
        if schedule.overlap_qwz:
            # external prep: the qwZ gather rides the host exchange and
            # the decoded compute params arrive as a program argument
            prep_params = None
        else:
            def prep_params(params):
                """Master params -> the compute-side replica the loss
                consumes: compute-dtype cast, then (qwZ) the stage-3
                gather rides int8/int4 blocks + fp16 scales and
                dequantizes on device — the master copy itself is never
                quantized."""
                cparams = cast(params, compute_dtype)
                if qwz is not None:
                    cparams = qwz.gather(cparams)
                return cparams

        def run_loss(p, batch, rng, pld_theta, loss_scale):
            """Shared scaled-loss body: returns (scaled_loss,
            (loss, caps)).  caps is {} unless layer-output hooks are
            registered (register_forward_hook) — then the model threads
            the requested block outputs out of the traced program as
            aux."""
            kwargs = {}
            if pld_enabled:
                kwargs = {"progressive_layer_drop": True,
                          "pld_theta": pld_theta}
            if capture is not None:
                kwargs["capture_layers"] = capture
            out = model.loss(p, batch, rng=rng, train=True, **kwargs)
            caps = {}
            if capture is not None:
                out, caps = out
            loss = out[0] if isinstance(out, tuple) else out
            scale_factor = loss_scale / (predivide if prescale else 1.0)
            return loss.astype(jnp.float32) * scale_factor, (loss, caps)

        # -- grad + reduce stage: implicit XLA psum vs the bucketed
        #    wire (in-program), vs the overlap wire's encode half
        wire_plan = eng.bucket_plan if capture is None else None
        if eng.bucket_plan is not None and wire_plan is None:
            log_dist("layer-output capture active: this step program "
                     "rides the implicit gradient wire (captures are "
                     "threaded through the global-loss trace)", ranks=[0])

        def implicit_grads(cparams, batch, rng, pld_theta, loss_scale):
            """Global-mean loss: XLA inserts one psum per grad leaf."""
            grads, (loss, caps) = jax.grad(
                lambda p: run_loss(p, batch, rng, pld_theta, loss_scale),
                has_aux=True)(cparams)
            return cast(grads, jnp.float32), loss, caps

        smap_kwargs = {}
        if wire_plan is not None:
            mesh = mesh_info.mesh
            P = PartitionSpec
            data_axes = mesh_info.data_axes  # outermost first
            batch_spec = mesh_info.data_spec
            inner_size = mesh_info.data_inner_size
            smap_kwargs = dict(mesh=mesh,
                               axis_names=mesh_info.manual_axes(data_axes),
                               check_vma=False)

            def _global_dp_rank():
                # linearized rank over the (possibly factored) data
                # axis: outer-major matches the mesh's device order
                if len(data_axes) == 1:
                    return jax.lax.axis_index(data_axes[0])
                return (jax.lax.axis_index(data_axes[0]) * inner_size
                        + jax.lax.axis_index(data_axes[1]))

            def _local_grads(cp, b, r, ls, th):
                # per-shard rng decorrelation: the implicit wire draws
                # ONE global dropout mask; each shard must not repeat it
                r = jax.random.fold_in(r, _global_dp_rank())
                grads, (loss, _) = jax.grad(
                    lambda p: run_loss(p, b, r, th, ls), has_aux=True)(cp)
                buckets = wire_plan.flatten(cast(grads, jnp.float32))
                return buckets, jax.lax.pmean(loss, data_axes)

            def _local_step(cp, b, r, ls, th):
                buckets, loss = _local_grads(cp, b, r, ls, th)
                return wire_plan.reduce(buckets), loss

            smapped = jax.shard_map(
                _local_step,
                in_specs=(P(), P(batch_spec), P(), P(), P()),
                out_specs=(wire_plan.bucket_out_specs(), P()),
                **smap_kwargs)

            def compute_grads(cparams, batch, rng, pld_theta, loss_scale):
                """LOCAL grads under shard_map, mean-reduced through the
                BucketPlan: one fused collective per bucket
                (psum_scatter under ZeRO>=2) instead of one psum per
                leaf."""
                buckets, loss = smapped(cparams, batch, rng, loss_scale,
                                        pld_theta)
                return wire_plan.unflatten(buckets), loss, {}
        else:
            compute_grads = implicit_grads

        # -- apply stage (shared core: fused tail == boundary apply) --

        def apply_core(params, opt_state, scaler_state, grads, lr,
                       gas_div):
            """Unscale -> overflow -> clip -> optimizer -> branchless
            skip-step -> ZeRO constraints -> loss-scale update.  The
            single body behind BOTH the boundary apply program and the
            fused/scan programs' in-program tail (gas_div folds the
            accumulation count into the unscale denominator)."""
            lr = select_lr(lr)
            loss_scale = scaler_state["cur_scale"]
            overflow = has_overflow(grads)
            denom = loss_scale * gas_div
            if prescale:
                denom = denom / predivide
            grads = jax.tree_util.tree_map(lambda g: g / denom, grads)
            grad_norm = jnp.asarray(0.0, jnp.float32)
            if clip > 0.0:
                grads, grad_norm = clip_grad_norm(grads, clip)
            extras = {}
            if store_grads:
                # zeroed on overflow: the step is skipped, so consumers
                # (e.g. GradientNoiseScale) must not ingest inf/nan
                extras["grads"] = jax.tree_util.tree_map(
                    lambda g: jnp.where(overflow, 0.0, g), grads)
            # grads here are already DP-averaged, so a 1-bit optimizer
            # on this path runs dense (comm_axis=None).  The compressed
            # hot path is engine._build_onebit_step: a shard_map fused
            # step with LOCAL grads where the optimizer owns the wire.
            new_params, new_opt = opt.update(grads, opt_state, params,
                                             lr=lr)

            # branchless skip-step on overflow (reference: step skipped,
            # scale halved — fp16/loss_scaler + stage2.py:1385-1404)
            sel = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
            new_params = sel(new_params, params)
            new_opt = sel(new_opt, opt_state)

            new_params = plan.constrain_params(new_params)
            new_opt = plan.constrain_opt_state(new_opt)
            new_scaler = scaler.jit_update(scaler_state, overflow)
            return (new_params, new_opt, new_scaler, overflow, grad_norm,
                    extras)

        def apply_step(params, opt_state, scaler_state, acc, lr):
            (new_params, new_opt, new_scaler, overflow, grad_norm,
             extras) = apply_core(params, opt_state, scaler_state, acc,
                                  lr, gas_div=gas)
            zero_acc = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return (new_params, new_opt, new_scaler, zero_acc, overflow,
                    grad_norm, extras)

        # -- compositions ---------------------------------------------

        def micro_step(cparams_or_params, acc, batch, rng, loss_scale,
                       pld_theta):
            if prep_params is not None:
                cparams = prep_params(cparams_or_params)
            else:
                cparams = cparams_or_params
            grads, loss, caps = compute_grads(cparams, batch, rng,
                                              pld_theta, loss_scale)
            new_acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            new_acc = plan.constrain_grads(new_acc)
            return loss, new_acc, {"layer_outputs": caps}

        def full_step(params, opt_state, scaler_state, batch, rng, lr,
                      pld_theta, cparams=None):
            """Whole training step (fwd+bwd+optimizer+scaler) as ONE
            program — the gas==1 fast path.  The split micro/apply pair
            writes the fp32 gradient tree to HBM at the end of one
            program and reads it back at the start of the next (plus a
            second host dispatch per step); here the gradients never
            outlive the fused program and XLA can overlap the optimizer
            with the tail of the backward."""
            loss_scale = scaler_state["cur_scale"]
            if prep_params is not None:
                cparams = prep_params(params)
            grads, loss, caps = compute_grads(cparams, batch, rng,
                                              pld_theta, loss_scale)
            grads = plan.constrain_grads(grads)
            (new_params, new_opt, new_scaler, overflow, grad_norm,
             extras) = apply_core(params, opt_state, scaler_state, grads,
                                  lr, gas_div=1)
            extras = dict(extras)
            extras["layer_outputs"] = caps
            return (new_params, new_opt, new_scaler, loss, overflow,
                    grad_norm, extras)

        def scan_batch_step(params, opt_state, scaler_state, batches,
                            rngs, lr, pld_theta, cparams=None):
            """Whole GLOBAL batch (gas micro steps + update) as ONE
            program: micro batches arrive stacked on a leading [gas]
            dim and a lax.scan accumulates grads — one host dispatch
            per global batch instead of gas+1 (train_batch uses this
            when the iterator is stackable)."""
            loss_scale = scaler_state["cur_scale"]
            if prep_params is not None:
                # the gather sits OUTSIDE the scan body: 1 event/batch
                cparams = prep_params(params)

            # captured layer outputs ride the scan CARRY (overwritten
            # per micro step — reference hooks overwrite per forward),
            # not the stacked ys: as ys they'd materialize a [gas, ...]
            # buffer per hooked layer only for the last slice to survive
            caps0 = {}
            if capture is not None:
                caps_struct = jax.eval_shape(
                    lambda p, b, r, ls, th: run_loss(p, b, r, th,
                                                     ls)[1][1],
                    cparams,
                    jax.tree_util.tree_map(lambda x: x[0], batches),
                    rngs[0], loss_scale, pld_theta)
                caps0 = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), caps_struct)

            def body(carry, inp):
                acc, _ = carry
                batch_i, rng_i = inp
                grads, loss, caps = compute_grads(cparams, batch_i,
                                                  rng_i, pld_theta,
                                                  loss_scale)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return (plan.constrain_grads(acc), caps), loss

            acc0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            acc0 = plan.constrain_grads(acc0)
            (acc, caps), losses = jax.lax.scan(body, (acc0, caps0),
                                               (batches, rngs))
            (new_params, new_opt, new_scaler, zero_acc, overflow,
             grad_norm, extras) = apply_step(params, opt_state,
                                             scaler_state, acc, lr)
            extras = dict(extras)
            extras["layer_outputs"] = caps
            return (new_params, new_opt, new_scaler, jnp.mean(losses),
                    overflow, grad_norm, extras)

        # -- overlap-wire composition: grads -> host exchange ->
        #    combine (runtime/comm/overlap.py drives the exchange) ----

        def build_overlap_fns():
            P = PartitionSpec
            mesh = mesh_info.mesh

            def _encode_local(cp, b, r, ls, th):
                buckets, loss = _local_grads(cp, b, r, ls, th)
                return wire_plan.overlap_encode(buckets), loss

            smapped_enc = jax.shard_map(
                _encode_local,
                in_specs=(P(), P(batch_spec), P(), P(), P()),
                out_specs=(wire_plan.overlap_encode_out_spec(), P()),
                **smap_kwargs)

            def grads_step(params, batch, rng, loss_scale, pld_theta):
                cparams = prep_params(params)
                payload, loss = smapped_enc(cparams, batch, rng,
                                            loss_scale, pld_theta)
                return loss, payload

            smapped_comb = jax.shard_map(
                wire_plan.overlap_combine, in_specs=(P(),),
                out_specs=wire_plan.bucket_out_specs(), **smap_kwargs)

            def combine_step(acc, matrix):
                buckets = smapped_comb(matrix)
                grads = wire_plan.unflatten(buckets)
                new_acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return plan.constrain_grads(new_acc)

            return (jax.jit(grads_step),
                    jax.jit(combine_step, donate_argnums=(0,)))

        # -- emit per the schedule ------------------------------------

        # under overlap_qwz the gather is EXTERNAL (its own counted
        # encode dispatch) — the serial compositions must not also
        # count a per-dispatch gather event
        qwz_int = None if schedule.overlap_qwz else qwz

        fns = {}
        donate_apply = jax.jit(apply_step, donate_argnums=(0, 1, 2, 3))
        fns["apply"] = self._counted(donate_apply, name="apply")
        # lr=None (optimizer-default) is a static arg value: jit treats
        # None as an empty pytree, giving that case its own single trace

        if schedule.overlap_wire:
            grads_fn, combine_fn = build_overlap_fns()
            fns["grads"] = self._counted(grads_fn, plan=wire_plan,
                                         wire_events=1, name="grads")
            fns["combine"] = self._counted(combine_fn, name="combine")
            log_dist(self._describe(schedule), ranks=[0])
            return fns

        donate_micro = jax.jit(micro_step, donate_argnums=(1,))
        fns["micro"] = self._counted(donate_micro, plan=wire_plan,
                                     wire_events=1, qwz=qwz_int,
                                     qwz_events=1, name="micro")
        if schedule.composition == "onebit":
            fns["full"] = self._counted(eng._build_onebit_step(cast),
                                        name="full")
        elif schedule.composition == "fused":
            # scaler state (arg 2) is NOT donated: it stays readable
            # between the fused forward and step(), so engine.loss_scale
            # keeps reference pre-update semantics until the boundary
            fns["full"] = self._counted(
                jax.jit(full_step, donate_argnums=(0, 1)),
                plan=wire_plan, wire_events=1, qwz=qwz_int, qwz_events=1,
                name="full")
        elif schedule.composition == "scan":
            fns["full_scan"] = self._counted(
                jax.jit(scan_batch_step, donate_argnums=(0, 1)),
                plan=wire_plan, wire_events=gas, qwz=qwz_int,
                qwz_events=1, name="full_scan")
        log_dist(self._describe(schedule), ranks=[0])
        return fns

    def _describe(self, schedule: StepSchedule) -> str:
        """Schedule log line, annotated when this build is the SERIAL
        rebuild after a coordinated runtime demotion of the overlap
        wire — a demoted run's logs must say why its schedule changed
        mid-run, not just that it did."""
        desc = schedule.describe()
        demoted = getattr(self.engine, "_demoted_reason", None)
        if demoted:
            desc += (" [rebuilt on the serial wire by runtime demotion: "
                     f"{demoted}]")
        return desc
