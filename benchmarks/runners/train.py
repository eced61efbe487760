"""Runs a training cell: `deepspeed_tpu.initialize`, then
`engine.forward` / `backward` / `step` on a fresh seeded batch every step,
one step in flight: dispatch step k, then read the loss of step k-1.

Copied from what ran on the chip in PR 22 (chip_smoke.py: `train_config`,
`build_engine`, `run_steps`).  Workload file keys: `engine` (the DeepSpeed
config; batch sizes and the mesh are filled in from the traffic mix and
the cell's chips), `model` (overrides of the family's model config, e.g.
`attn_impl`), `warmup_steps`, `check` (`loss`: `first_step` or
`eval_batch`; `rtol` for the loss at the initial weights; for the first
optimizer step either `step_rtol`, how far the loss fell beside the
reference's own step, or `update`, bands for the step itself beside the
reference's (reference/adam_step.py), where dropout keeps the two steps
from being the same; each with its `why`), `trace` (`skip_steps`,
`steps`).

The window opens with the device idle and closes when the last step
dispatched before `--seconds` ran out has finished, so every step counted
ran wholly inside it: tokens/s = steps * tokens a step / window seconds.
"""

from __future__ import annotations

import contextlib
import gc
import math
import os
import time

from benchmarks.harness import (GcPauses, RunResult, Spans, StallWatch,
                                peak_bytes, plugin, seed_key, start_trace,
                                stop_trace)

STALL_S = 1.0  # a step this much longer than the median is reported as one


def run(cell) -> RunResult:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm import make_mesh

    w, traffic, n = cell.workload, cell.traffic, cell.chips
    seq, micro = traffic["seq_len"], traffic["micro_batch"]
    model = cell.family.build(cell.config, seq_len=seq, n_dev=n,
                              **w.get("model", {}))
    ds = dict(w["engine"], train_batch_size=micro * n,
              train_micro_batch_size_per_gpu=micro, mesh={"data": n},
              steps_per_print=0)
    mesh = make_mesh(devices=cell.devices)
    # weights made where the engine wants them: every chip its own copy
    init = jax.jit(model.init, out_shardings=mesh.replicated())
    checksum = jax.jit(lambda t: sum(
        jnp.sum(jnp.abs(a.astype(jnp.float32)))
        for a in jax.tree_util.tree_leaves(t)))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config_params=ds, mpu=mesh,
        model_parameters=init(seed_key(cell.seed)))
    weights_sum = float(checksum(engine.params))
    batches = cell.generator.batches(traffic, seed=cell.seed, chips=n,
                                     config=cell.config, family=cell.family)
    tokens_per_step = cell.generator.tokens_per_step(traffic, n)
    spans = Spans()

    def step(batch):
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        return loss

    # set-up: the checked losses (at the initial weights, and on the same
    # batch after one step on it), then every program the window will run
    # (on a mesh the second step compiles the steady one)
    first = next(batches)
    check, system_after = w["check"], None
    if check["loss"] == "eval_batch":  # dropout off, as the reference
        system_loss = float(engine.eval_batch(first))
        float(step(first))
        system_loss_1 = float(engine.eval_batch(first))
        if "update" in check:  # the moved weights, kept on the host
            system_after = jax.device_get(engine.params)
        warmed = 1
    else:                              # no dropout: the step's own loss
        system_loss = float(step(first))
        system_loss_1 = float(step(first))
        warmed = 2
    for _ in range(w["warmup_steps"] - warmed):
        float(step(next(batches)))
    compiled_before = len(cell.compiles)
    setup_s = time.perf_counter() - cell.t_start

    # the window
    tr = w["trace"] if cell.trace else None
    trace_dir, trace_path = os.path.join(cell.scratch, "trace"), None
    losses, done, pending, k = [], [], None, 0
    window = contextlib.ExitStack()
    t0 = time.perf_counter()
    with GcPauses() as gc_pauses, StallWatch() as watch:
        while time.perf_counter() - t0 < cell.seconds:
            watch.beat()
            if tr and k == tr["skip_steps"]:
                pending.block_until_ready()  # the traced steps start from idle
                start_trace(trace_dir)
                window.enter_context(jax.profiler.TraceAnnotation("bench.window"))
            with spans.span("bench.feed"):
                batch = next(batches)
            with spans.span("bench.dispatch"):
                loss = step(batch)
            if pending is not None:
                with spans.span("bench.read_loss"):
                    losses.append(float(pending))
                done.append(time.perf_counter())
            pending, k = loss, k + 1
            if tr and k == tr["skip_steps"] + tr["steps"]:
                with spans.span("bench.read_loss"):
                    pending.block_until_ready()
                window.close()
                trace_path = stop_trace(trace_dir)
    losses.append(float(pending))
    done.append(time.perf_counter())
    window.close()
    if tr and trace_path is None:
        trace_path = stop_trace(trace_dir)
    window_s = done[-1] - t0
    compiles_in_window = len(cell.compiles) - compiled_before
    rate = len(done) * tokens_per_step / window_s / n
    flops = cell.family.model_flops_per_token(cell.config, seq)
    peak = peak_bytes(cell.devices)

    # correctness, outside the window: the plain reference on the first
    # batch, from the initial weights (made again from the seed and checked
    # to be the ones the engine started from) and after its own first
    # optimizer step on that batch
    optimizer = w["engine"]["optimizer"]["params"]
    del engine
    gc.collect()
    ref = plugin("reference", cell.config["family"])
    params = init(seed_key(cell.seed))
    same_weights = float(checksum(params)) == weights_sum
    stages = lambda tree: ref.stages(tree, first,
                                     **ref.for_config(cell.config))
    ref_loss, ref_loss_1, update = plugin("reference", "adam_step") \
        .losses_around_first_step(
            stages(params),
            system_after=system_after and stages(system_after)[:2],
            **{k: optimizer[k] for k in ("lr", "eps", "weight_decay")
               if k in optimizer})
    rel = abs(system_loss - ref_loss) / abs(ref_loss)
    fell = (system_loss - system_loss_1) / (ref_loss - ref_loss_1)
    finite = all(math.isfinite(x) for x in losses)
    step_ok = ("step_rtol" not in check
               or abs(fell - 1.0) <= check["step_rtol"]) and all(
        lo <= update[k] <= hi for k, (lo, hi) in check.get(
            "update", {}).items())
    correct = (same_weights and finite and rel <= check["rtol"]
               and step_ok and compiles_in_window == 0)
    step_ms = 1e3 * np.diff([t0] + done)
    slow = np.flatnonzero(step_ms > 1.5 * np.median(step_ms))
    # a stalled step: which of the loop's three calls held it, and what
    # the host did meanwhile.  Step i ends with the read in iteration i+1.
    def held_ms(i):
        calls = (("feed", i + 1), ("dispatch", i + 1), ("read_loss", i))
        return {} if tr else {
            name + "_ms": round(1e3 * spans.seconds["bench." + name][j], 1)
            for name, j in calls
            if j < len(spans.seconds.get("bench." + name, []))}

    stalls = [{"step": int(i), "ms": round(float(step_ms[i]), 1),
               **held_ms(i), **watch.between(([t0] + done)[i], done[i])}
              for i in np.flatnonzero(
                  step_ms > np.median(step_ms) + 1e3 * STALL_S)]
    notes = [{"steps": len(done), "window_s": window_s,
              "tokens_per_step": tokens_per_step,
              "step_ms_median": float(np.median(step_ms)),
              "step_ms_max": float(step_ms.max()),
              "slow_steps": {int(i): round(float(step_ms[i]), 1)
                             for i in slow[:20]},
              "gc_pauses_over_50ms": gc_pauses.over(0.05),
              "stalls": stalls[:5],
              "watch_late_ms_max": watch.between(t0, done[-1]).get(
                  "watch_late_ms_max"),
              "model_flops_per_token": flops,
              "mfu_pct": 100.0 * rate * flops / cell.peaks["bf16_flops_per_s"],
              "compiles_in_window": compiles_in_window,
              "compiles_in_setup": compiled_before,
              "compile_s_in_setup": sum(cell.compiles[:compiled_before])},
             {"check": "loss", "system_loss": system_loss,
              "reference_loss": ref_loss, "relative_difference": rel,
              "rtol": check["rtol"], "system_loss_after_one_step":
              system_loss_1, "reference_loss_after_one_step": ref_loss_1,
              "fell_over_reference_fell": fell,
              "step_rtol": check.get("step_rtol"),
              "update_against_reference": update,
              "update_bands": check.get("update"),
              "same_initial_weights": same_weights,
              "losses_finite": finite, "first_loss": losses[0],
              "last_loss": losses[-1]}]
    return RunResult(
        end_to_end={"train_tokens_per_s": rate, "setup_s": setup_s},
        correct=correct, attempted=len(done),
        failed=sum(not math.isfinite(x) for x in losses), notes=notes,
        memory_peak_bytes=peak,
        host_spans=spans.seconds, trace_path=trace_path,
        shapes={"batch": micro, "seq_len": seq, "steps_traced":
                tr["steps"] if tr else 0})
