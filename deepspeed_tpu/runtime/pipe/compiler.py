"""Pipeline schedule compiler — flat per-rank programs for the 1F1B walk.

The interpreted canonical walk spends its time in
serialized Python per schedule event (schedule-stream regeneration +
dependency re-simulation + isinstance dispatch + counter/dict/mail
bookkeeping, every train_batch), 12-16 % of step time on CPU-mesh grains
and projected ~150 ms/step at 8 stages x 16 micro batches. This module
removes the interpreter from that inner loop:

* `compile_schedule` lowers the canonical event order (the output of
  engine._simulate_order — identical on every process, the property that
  keeps the channel handoffs deadlock-free) ONCE into a flat, immutable
  program: parallel tuples of opcode / model-chunk / micro-id / buffer
  slots.  Micro ids are precomputed, so the run-time recv/send/fwd/bwd
  counters disappear entirely.

* every Send+Recv pair is FUSED into a single transfer op placed at the
  send's position.  The data transfer already happens at the send event
  in the interpreted walk (the recv is pure mail-dict bookkeeping), so
  the collective entry order across processes is unchanged — only the
  Python disappears.  Fusion is made unconditionally safe by giving the
  fused write a liveness-fresh buffer slot (below) instead of the
  schedule's recv-time slot.

* buffer slots are resolved once by liveness analysis into preallocated
  per-stage pools (plain lists — the double-buffered pool): each
  (chunk, micro) value gets a slot live from its writing event to its
  last reading event.  No dict hashing, no (mc, mb) tuple keys, no mail
  dict at run time.

* `bind_program` turns the flat program into a list of zero-argument
  closures with every static decision (stage runtime, slot indices, rng
  fold constants, transfer plans/shardings) resolved at bind time.  The
  executor loop in engine.py is then `for f in steps: f()` — it touches
  no Python objects besides the program list and the pools.  On
  multi-host ranks, events with no local role are pruned at bind time
  (the interpreted walk pays Python for every remote event).

The interpreted walk stays available as `pipeline.debug_schedule: true`
— the parity oracle (tests pin bit-identical losses) and the
reference-shaped executor for new-instruction bring-up.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax

from ...monitor.counters import COUNTERS, tree_bytes
from .p2p import batch_shardable
from .schedule import (BackwardPass, ForwardPass, LoadMicroBatch,
                       OptimizerStep, RecvActivation, RecvGrad, ReduceGrads,
                       ReduceTiedGrads, SendActivation, SendGrad)

# opcodes (flat-program ISA)
OP_LOAD = 0        # (mc, mb, x_slot)
OP_FWD = 1         # (mc, mb, x_slot, y_slot)   y_slot < 0: output unused
OP_XFER_ACT = 2    # (src_mc, mb, y_slot, dst_x_slot)    fused send+recv
OP_BWD = 3         # (mc, mb, x_slot, dy_slot, dx_slot)  dy<0: last stage
OP_XFER_GRAD = 4   # (src_mc, mb, dx_slot, dst_dy_slot)  fused send+recv
OP_TIED = 5        # ()
OP_STEP = 6        # ()

OP_NAMES = {OP_LOAD: "load", OP_FWD: "fwd", OP_XFER_ACT: "xfer_act",
            OP_BWD: "bwd", OP_XFER_GRAD: "xfer_grad", OP_TIED: "tied",
            OP_STEP: "step"}


class PipeProgram:
    """Immutable lowered schedule: one entry per executed event.

    events: tuple of tuples — (op, mc, mb, a, b, c) with slot fields per
    the opcode table above (unused fields -1).  pool_sizes maps
    (mc, kind) -> required slot count, kind in {x, y, dy, dx}; the `x`
    pool also carries the forward rng (identical liveness).
    """

    __slots__ = ("events", "pool_sizes", "n_mc", "micro_batches",
                 "n_source_events")

    def __init__(self, events, pool_sizes, n_mc, micro_batches,
                 n_source_events):
        self.events = tuple(events)
        self.pool_sizes = dict(pool_sizes)
        self.n_mc = n_mc
        self.micro_batches = micro_batches
        # pre-fusion event count (for dispatch-rate accounting)
        self.n_source_events = n_source_events

    def __repr__(self):
        ops = ", ".join(OP_NAMES[e[0]] for e in self.events[:8])
        return (f"PipeProgram({len(self.events)} events from "
                f"{self.n_source_events}, n_mc={self.n_mc}, "
                f"M={self.micro_batches}, [{ops}...])")


def schedule_occupancy(streams) -> List[Dict[str, Any]]:
    """Per-physical-stage bubble/occupancy accounting from the canonical
    per-stage tick streams (`engine._pipe_streams()` output — the same
    object `compile_schedule` lowers).  A tick is `compute` when it
    carries a Forward/BackwardPass; the bubble fraction is the idle-tick
    share of the stage's stream — the schedule-theoretic pipeline bubble
    ((P-1)/(M+P-1) for plain 1F1B), independent of hardware timing.
    Emitted into every step event by the pipeline engine so a run's
    JSONL records how much of its step is schedule-structural."""
    out = []
    for stage, stream in enumerate(streams):
        ticks = len(stream)
        compute = 0
        for tick in stream:
            cmds = tick if isinstance(tick, (list, tuple)) else (tick,)
            if any(isinstance(c, (ForwardPass, BackwardPass))
                   for c in cmds):
                compute += 1
        out.append({"stage": stage, "ticks": ticks,
                    "compute_ticks": compute,
                    "bubble_frac": round(1.0 - compute / max(1, ticks), 4)})
    return out


class PipeInstrument:
    """Measured per-op dispatch-time accounting for the bound executor.

    Wraps every bound closure in a perf_counter pair, accumulating
    seconds by opcode and by model chunk.  This measures HOST dispatch
    time (dispatch is async); the engine closes the whole batch on a
    block_until_ready marker, so batch wall minus dispatch total bounds
    the device-side remainder — both land in the step event.  Only
    attached when a
    RunMonitor is active: the unmonitored executor keeps its bare
    `for f in steps: f()` loop."""

    __slots__ = ("op_s", "stage_s")

    def __init__(self):
        self.op_s: Dict[str, float] = {}
        self.stage_s: Dict[int, float] = {}

    def wrap(self, opname: str, mc: int, fn: Callable[[], None]):
        op_s, stage_s, clock = self.op_s, self.stage_s, time.perf_counter

        def timed():
            t0 = clock()
            fn()
            dt = clock() - t0
            op_s[opname] = op_s.get(opname, 0.0) + dt
            if mc >= 0:
                stage_s[mc] = stage_s.get(mc, 0.0) + dt
        return timed

    def drain(self) -> Dict[str, Any]:
        out = {
            "op_ms": {k: round(v * 1000.0, 3)
                      for k, v in sorted(self.op_s.items())},
            "stage_ms": {str(k): round(v * 1000.0, 3)
                         for k, v in sorted(self.stage_s.items())},
        }
        self.op_s.clear()
        self.stage_s.clear()
        return out


def compile_schedule(events, mc_of: Callable[[int, Any], int], n_mc: int,
                     micro_batches: int) -> PipeProgram:
    """Lower a canonical (stage, instruction) event list to a PipeProgram.

    `events` is engine._simulate_order's output; `mc_of` maps
    (stage, cmd) to the model-chunk index (engine._mc).  Pure structural
    lowering — no engine state is touched, so the result is reusable for
    every train_batch with the same (M, stages, interleave).
    """
    # -- pass 1: assign micro ids with the same counters the interpreted
    # dispatch uses, and drop bookkeeping-only instructions --------------
    events = list(events)
    fwd_cnt = [0] * n_mc
    bwd_cnt = [0] * n_mc
    sent_act = [0] * n_mc
    sent_grad = [0] * n_mc
    recv_act = [0] * n_mc
    recv_grad = [0] * n_mc
    load_cnt = 0
    mid: List[Tuple[int, int, int]] = []   # (kind, mc, mb)
    # one OP_TIED / OP_STEP per batch, placed at the LAST canonical
    # occurrence: every stage's stream carries one of each, and only at
    # the last one (stage 0's, after the globally final backward) are all
    # gradients complete.  Emitting at the first occurrence would apply
    # the optimizer while earlier stages' cooldown backwards are still
    # accumulating — dropped gradients this step, leakage into the next.
    tied_left = sum(isinstance(c, ReduceTiedGrads) for _, c in events)
    step_left = sum(isinstance(c, OptimizerStep) for _, c in events)
    n_source = 0
    for s, cmd in events:
        n_source += 1
        mc = mc_of(s, cmd)
        if isinstance(cmd, LoadMicroBatch):
            mid.append((OP_LOAD, mc, load_cnt))
            load_cnt += 1
        elif isinstance(cmd, ForwardPass):
            mid.append((OP_FWD, mc, fwd_cnt[mc]))
            fwd_cnt[mc] += 1
        elif isinstance(cmd, SendActivation):
            mid.append((OP_XFER_ACT, mc, sent_act[mc]))
            sent_act[mc] += 1
        elif isinstance(cmd, RecvActivation):
            # fused into the matching send (the transfer happens at the
            # send position in the interpreted walk too); assert the
            # canonical order really delivered before consumption
            mb = recv_act[mc]
            recv_act[mc] += 1
            if sent_act[mc - 1] < mb + 1:
                raise AssertionError(
                    f"recv_act before send for chunk {mc} micro {mb}")
        elif isinstance(cmd, BackwardPass):
            mid.append((OP_BWD, mc, bwd_cnt[mc]))
            bwd_cnt[mc] += 1
        elif isinstance(cmd, SendGrad):
            mid.append((OP_XFER_GRAD, mc, sent_grad[mc]))
            sent_grad[mc] += 1
        elif isinstance(cmd, RecvGrad):
            mb = recv_grad[mc]
            recv_grad[mc] += 1
            if sent_grad[mc + 1] < mb + 1:
                raise AssertionError(
                    f"recv_grad before send for chunk {mc} micro {mb}")
        elif isinstance(cmd, ReduceTiedGrads):
            tied_left -= 1
            if tied_left == 0:
                mid.append((OP_TIED, -1, -1))
        elif isinstance(cmd, OptimizerStep):
            step_left -= 1
            if step_left == 0:
                mid.append((OP_STEP, -1, -1))
        elif isinstance(cmd, ReduceGrads):
            pass  # within-stage dp reduction is implicit in the jitted loss
        else:
            raise NotImplementedError(f"instruction {cmd!r}")

    # -- pass 2: find each value's last reader (liveness) ----------------
    # value keys: ("x"|"y"|"dy"|"dx", mc, mb)
    last_read: Dict[Tuple[str, int, int], int] = {}
    for i, (kind, mc, mb) in enumerate(mid):
        if kind == OP_FWD:
            last_read[("x", mc, mb)] = i          # read again by BWD below
        elif kind == OP_XFER_ACT:
            last_read[("y", mc, mb)] = i
        elif kind == OP_BWD:
            last_read[("x", mc, mb)] = i
            last_read[("dy", mc, mb)] = i
        elif kind == OP_XFER_GRAD:
            last_read[("dx", mc, mb)] = i

    # -- pass 3: slot allocation + final event emission ------------------
    free: Dict[Tuple[int, str], List[int]] = {}
    high: Dict[Tuple[int, str], int] = {}
    slot_of: Dict[Tuple[str, int, int], int] = {}

    def alloc(kind, mc, mb):
        pool = free.setdefault((mc, kind), [])
        if pool:
            s = pool.pop()
        else:
            s = high.get((mc, kind), 0)
            high[(mc, kind)] = s + 1
        slot_of[(kind, mc, mb)] = s
        return s

    def read(kind, mc, mb, i):
        s = slot_of[(kind, mc, mb)]
        if last_read.get((kind, mc, mb)) == i:
            free.setdefault((mc, kind), []).append(s)
        return s

    out: List[Tuple[int, int, int, int, int]] = []
    for i, (kind, mc, mb) in enumerate(mid):
        if kind == OP_LOAD:
            out.append((OP_LOAD, mc, mb, alloc("x", mc, mb), -1, -1))
        elif kind == OP_FWD:
            x = read("x", mc, mb, i)
            y = -1
            if ("y", mc, mb) in last_read:      # someone will send it
                y = alloc("y", mc, mb)
            out.append((OP_FWD, mc, mb, x, y, -1))
        elif kind == OP_XFER_ACT:
            y = read("y", mc, mb, i)
            x = alloc("x", mc + 1, mb)
            out.append((OP_XFER_ACT, mc, mb, y, x, -1))
        elif kind == OP_BWD:
            x = read("x", mc, mb, i)
            dy = (read("dy", mc, mb, i)
                  if ("dy", mc, mb) in slot_of else -1)
            dx = (alloc("dx", mc, mb)
                  if ("dx", mc, mb) in last_read else -1)
            out.append((OP_BWD, mc, mb, x, dy, dx))
        elif kind == OP_XFER_GRAD:
            dx = read("dx", mc, mb, i)
            dy = alloc("dy", mc - 1, mb)
            out.append((OP_XFER_GRAD, mc, mb, dx, dy, -1))
        else:
            out.append((kind, -1, -1, -1, -1, -1))

    pool_sizes = {k: v for k, v in high.items()}
    return PipeProgram(out, pool_sizes, n_mc, micro_batches, n_source)


# ---------------------------------------------------------------------------
# binding: flat program -> list of zero-arg closures
# ---------------------------------------------------------------------------

def _leaf_shardings(rt, avals):
    """Per-leaf placement tree for a payload landing on stage rt — the
    SAME batch_shardable rule the interpreted path applies per event,
    resolved once here."""
    G = len(rt.devices)
    return jax.tree_util.tree_map(
        lambda a: rt.batch_sharding if batch_shardable(a.shape, G)
        else rt.replicated, avals)


def bind_program(engine, prog: PipeProgram, out_avals,
                 instrument: Optional[PipeInstrument] = None
                 ) -> List[Callable]:
    """Lower a PipeProgram to executable closures against `engine`.

    out_avals[mc] is the output aval tree of model chunk mc (from
    engine._chunk_out_avals).  Every static decision — stage runtime,
    slot index, rng fold constant, device_put sharding or channel
    transfer plan — is resolved here; the returned closures only index
    pools and call the already-jitted stage programs.  Closures read
    mutable engine/runtime state (params, scaler, micro-batch cache)
    through attribute access so checkpoint reloads keep working.

    Multi-host: events with no local role on this process are pruned
    (channel ops keep their collective entry order — both endpoints bind
    them at the same program positions).

    instrument: optional PipeInstrument — wraps every bound closure in
    per-op dispatch timing (attached by the engine when a RunMonitor is
    active; None keeps the closures bare).
    """
    mh = engine._mh
    n_mc = prog.n_mc
    fold_in = jax.random.fold_in

    def rt_of(mc):
        if mh:
            return engine._local.get(mc)
        return engine.stages[mc]

    # preallocated double-buffered pools (the x pool rides rng + x)
    pools: Dict[Tuple[int, str], List[Any]] = {
        k: [None] * n for k, n in prog.pool_sizes.items()}
    rngs: Dict[int, List[Any]] = {
        mc: [None] * n for (mc, kind), n in prog.pool_sizes.items()
        if kind == "x"}
    labels_pool: List[Any] = [None] * prog.micro_batches

    steps: List[Callable[[], None]] = []

    def push(f, opname, mc):
        steps.append(f if instrument is None
                     else instrument.wrap(opname, mc, f))

    for op, mc, mb, a, b, c in prog.events:
        if op == OP_LOAD:
            rt = rt_of(mc)
            if rt is None:
                continue
            xp, slot = pools[(mc, "x")], a
            place = rt.place_batch

            def f_load(eng=engine, xp=xp, slot=slot, mb=mb, place=place):
                xp[slot] = place(eng._mb_cache[mb][0])
            push(f_load, "load", mc)
        elif op == OP_FWD:
            rt = rt_of(mc)
            if rt is None:
                continue
            xp, rp = pools[(mc, "x")], rngs[mc]
            fold_const = mb * n_mc + mc
            if rt.is_last:
                def f_fwd_last(eng=engine, rt=rt, xp=xp, rp=rp, slot=a,
                               mb=mb, fc=fold_const, fold_in=fold_in,
                               labels_pool=labels_pool):
                    rng = fold_in(eng._batch_key, fc)
                    rp[slot] = rng
                    labels = rt.place_batch(
                        np.asarray(eng._mb_cache[mb][1]))
                    labels_pool[mb] = labels
                    rt.losses.append(rt.loss_j(rt.own, rt.ro_tied,
                                               xp[slot], labels, rng))
                push(f_fwd_last, "fwd", mc)
            else:
                yp = pools.get((mc, "y"))
                def f_fwd(eng=engine, rt=rt, xp=xp, rp=rp, yp=yp,
                          xs=a, ys=b, fc=fold_const, fold_in=fold_in):
                    rng = fold_in(eng._batch_key, fc)
                    rp[xs] = rng
                    y = rt.fwd_j(rt.own, rt.ro_tied, xp[xs], rng)
                    if ys >= 0:
                        yp[ys] = y
                push(f_fwd, "fwd", mc)
        elif op == OP_BWD:
            rt = rt_of(mc)
            if rt is None:
                continue
            xp, rp = pools[(mc, "x")], rngs[mc]
            dxp = pools.get((mc, "dx"))
            if rt.is_last:
                def f_bwd_last(eng=engine, rt=rt, xp=xp, rp=rp, dxp=dxp,
                               xs=a, dxs=c, mb=mb, labels_pool=labels_pool):
                    x = xp[xs]
                    xp[xs] = None
                    rng = rp[xs]
                    rp[xs] = None
                    labels = labels_pool[mb]
                    labels_pool[mb] = None
                    scale = eng._scaler_state["cur_scale"]
                    dx, rt.acc, rt.acc_ro = rt.bwd_j(
                        rt.own, rt.ro_tied, x, labels, rng, scale,
                        rt.acc, rt.acc_ro)
                    if dxs >= 0:
                        dxp[dxs] = dx
                push(f_bwd_last, "bwd", mc)
            else:
                dyp = pools[(mc, "dy")]
                def f_bwd(rt=rt, xp=xp, rp=rp, dyp=dyp, dxp=dxp,
                          xs=a, dys=b, dxs=c):
                    x = xp[xs]
                    xp[xs] = None
                    rng = rp[xs]
                    rp[xs] = None
                    dy = dyp[dys]
                    dyp[dys] = None
                    dx, rt.acc, rt.acc_ro = rt.bwd_j(
                        rt.own, rt.ro_tied, x, rng, dy, rt.acc, rt.acc_ro)
                    if dxs >= 0:
                        dxp[dxs] = dx
                push(f_bwd, "bwd", mc)
        elif op == OP_XFER_ACT:
            f = _bind_xfer(engine, mh, src_mc=mc, dst_mc=mc + 1,
                           avals=out_avals[mc],
                           src_pool=pools.get((mc, "y")), src_slot=a,
                           dst_pool=pools[(mc + 1, "x")], dst_slot=b,
                           chan=(engine._chan_act.get(mc) if mh else None),
                           rt_of=rt_of, kind="act")
            if f is not None:
                push(f, "xfer_act", mc)
        elif op == OP_XFER_GRAD:
            f = _bind_xfer(engine, mh, src_mc=mc, dst_mc=mc - 1,
                           avals=out_avals[mc - 1],
                           src_pool=pools.get((mc, "dx")), src_slot=a,
                           dst_pool=pools[(mc - 1, "dy")], dst_slot=b,
                           chan=(engine._chan_grad.get(mc) if mh else None),
                           rt_of=rt_of, kind="grad")
            if f is not None:
                push(f, "xfer_grad", mc)
        elif op == OP_TIED:
            push(engine._reduce_tied_grads_mh if mh
                 else engine._reduce_tied_grads, "tied", -1)
        elif op == OP_STEP:
            push(engine._pipe_optimizer_step_mh if mh
                 else engine._pipe_optimizer_step, "step", -1)
        else:
            raise NotImplementedError(f"opcode {op}")
    return steps


def _bind_xfer(engine, mh, src_mc, dst_mc, avals, src_pool, src_slot,
               dst_pool, dst_slot, chan, rt_of, kind="act"):
    """One fused send+recv: returns a closure or None (no local role).
    Payload bytes are resolved from the avals ONCE here and counted per
    dispatch (`pipe.xfer_{kind}`); the channel (mh) paths count inside
    ChannelPlan instead."""
    if not mh:
        # single-controller: a device_put resharding, target layout
        # resolved once from the aval (the interpreted path re-derives it
        # per event from the runtime value's shape)
        rt_dst = rt_of(dst_mc)
        sh = _leaf_shardings(rt_dst, avals)
        device_put = jax.device_put
        nbytes = tree_bytes(avals)
        cname = f"pipe.xfer_{kind}"

        def f_put(sp=src_pool, ss=src_slot, dp=dst_pool, ds=dst_slot,
                  sh=sh, device_put=device_put, nbytes=nbytes, cname=cname):
            COUNTERS.add(cname, nbytes)
            y = sp[ss]
            sp[ss] = None
            dp[ds] = device_put(y, sh)
        return f_put
    if chan is None:
        return None  # this process is not an endpoint: prune
    plan = chan.plan(avals)
    src_local = rt_of(src_mc) is not None
    dst_local = rt_of(dst_mc) is not None
    if src_local and dst_local:
        def f_both(sp=src_pool, ss=src_slot, dp=dst_pool, ds=dst_slot,
                   plan=plan):
            y = sp[ss]
            sp[ss] = None
            dp[ds] = plan(y)
        return f_both
    if src_local:
        def f_src(sp=src_pool, ss=src_slot, plan=plan):
            y = sp[ss]
            sp[ss] = None
            plan(y)
        return f_src

    def f_dst(dp=dst_pool, ds=dst_slot, plan=plan):
        dp[ds] = plan(None)
    return f_dst
