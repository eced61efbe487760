"""Runs a serving cell: `ServeEngine` behind a `ServeWorker`, requests
submitted by `engine.submit` from this thread on the traffic mix's
schedule, open loop.

Copied from tools/serve_bench.py::run_lane, with three changes: latency
is timed from the instant a request was DUE, not from `submit`; how late
the generator ran is reported; and every output token is stamped by the
benchmark's own clock (`TokenObserver`), not by the program: the stamps
the engine writes into a `Request` are the code under test, and a change
to where it takes them would move the yardstick.  Workload file keys:
`serve` (the `ServeConfig` fields), `model` (overrides of the family's
model config), `drain_seconds`, `check` (`requests`: at most so many
finished requests are checked, `batch`: so many in one pass of the
reference, `logit_margin` and its `why`), `trace` (`seconds`: the last
stretch of sending is traced).

Sending lasts `--seconds`; then the run drains for at most
`drain_seconds`.  A request shed, errored or unfinished by then failed,
and its time to first token counts as the window's length.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

from benchmarks.harness import (RunResult, Spans, due_latencies_ms,
                                numpy_seed, peak_bytes, percentile, plugin,
                                seed_key, start_trace, stop_trace,
                                token_gaps_ms)


class TokenObserver(threading.Thread):
    """The benchmark's clock on every output token.  Looks at `len(r.out)`
    of each request still running every `tick` seconds and stamps what is
    new: a token exists for the user once the program has appended it,
    whatever the program does before or after.  `times[i]` are the stamps
    of request i's tokens, `finished[i]` when it was first seen done.

    The tick is a trade measured on the chip (PR 24): every wake-up takes
    the interpreter's lock from the engine's thread, and at 0.5 ms that
    lengthened each 140 ms decode step by 3.3 ms and widened the spread of
    the first-token tail from 3-6 % to 11 %.  At 5 ms a stamp is late by
    2.5 ms on average, the same on both sides of a comparison."""

    def __init__(self, tick: float = 0.005):
        super().__init__(daemon=True)
        self.tick = tick
        self.times, self.finished = [], []
        self.late_max = 0.0  # how late the observer itself woke, at worst
        self._reqs, self._live = [], []
        self._lock = threading.Lock()
        self._halt = threading.Event()

    def watch(self, req) -> None:
        with self._lock:
            self._live.append(len(self._reqs))
            self._reqs.append(req)
            self.times.append([])
            self.finished.append(None)

    def look(self, now: float) -> None:
        with self._lock:
            live = list(self._live)
        for i in live:
            r = self._reqs[i]
            done = r.done           # before the length: no token is missed
            new = len(r.out) - len(self.times[i])
            if new > 0:
                self.times[i].extend([now] * new)
            if done:
                self.finished[i] = now
                with self._lock:
                    self._live.remove(i)

    def run(self) -> None:
        last = time.perf_counter()
        while not self._halt.wait(self.tick):
            now = time.perf_counter()
            self.late_max = max(self.late_max, now - last - self.tick)
            last = now
            self.look(now)

    def stop(self) -> None:
        self._halt.set()
        self.join()
        self.look(time.perf_counter())


def _check_outputs(cell, model, params, reqs, check):
    """Teacher-forced through the plain reference, `batch` requests a
    pass: at every generated position the token the engine chose must
    have a reference logit within `logit_margin` of the reference's
    largest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = plugin("reference", cell.config["family"])
    kw = ref.for_config(cell.config)
    rng = np.random.RandomState(numpy_seed(cell.seed, stream=1))
    done = [r for r in reqs if r.state == "finished" and r.out]
    picked = [done[i] for i in
              rng.permutation(len(done))[:check["requests"]]]
    width, rows = model.config.max_seq_len, check["batch"]

    @jax.jit
    def gaps(lg, chosen):  # lg [B, S, V], chosen [B, S] -> two [B, S]
        at = jnp.take_along_axis(lg, chosen[..., None], axis=-1)[..., 0]
        return lg.max(axis=-1) - at, lg.argmax(axis=-1) == chosen

    worst, agree, total = 0.0, 0, 0
    for at in range(0, len(picked), rows):
        group = picked[at:at + rows]
        toks = np.zeros((rows, width), np.int32)
        chosen = np.zeros((rows, width), np.int32)
        mask = np.zeros((rows, width), bool)
        for j, r in enumerate(group):
            seq = (r.prompt + r.out)[:width]
            toks[j, :len(seq)] = seq
            first = len(r.prompt) - 1  # the position that chose out[0]
            chosen[j, first:first + len(r.out)] = r.out
            mask[j, first:first + len(r.out)] = True
        gap, same = gaps(ref.logits(params, jnp.asarray(toks), **kw),
                         jnp.asarray(chosen))
        worst = max(worst, float(np.asarray(gap)[mask].max()))
        agree += int(np.asarray(same)[mask].sum())
        total += int(mask.sum())
    ok = bool(picked) and worst <= check["logit_margin"]
    return ok, {"check": "logits", "requests": len(picked),
                "positions": total, "worst_gap_to_top_logit": worst,
                "logit_margin": check["logit_margin"],
                "top1_agreement": agree / max(total, 1)}


def run(cell) -> RunResult:
    import jax

    from deepspeed_tpu.monitor.counters import COUNTERS
    from deepspeed_tpu.serving import ServeConfig, ServeEngine, ServeWorker

    w, traffic = cell.workload, cell.traffic
    serve = ServeConfig(**w["serve"])
    model = cell.family.build(cell.config, seq_len=serve.max_seq_len,
                              n_dev=1, **w.get("model", {}))
    params = jax.jit(model.init)(seed_key(cell.seed))
    engine = ServeEngine(model, params, serve)
    timeline = cell.generator.timeline(traffic, seed=cell.seed,
                                       seconds=cell.seconds,
                                       config=cell.config, family=cell.family)
    # set-up: the two programs (one prefill chunk shape, one decode shape)
    engine.generate([timeline[0][1][:serve.prefill_chunk + 1]], 2)
    recorder = None
    if cell.trace:
        from deepspeed_tpu.monitor.tracing import TraceRecorder

        recorder = TraceRecorder(os.path.join(cell.scratch, "spans"),
                                 buffer_events=1 << 20, sample_rate=1.0)
        engine.attach_tracing(tracer=recorder)
    compiled_before = len(cell.compiles)
    counters_before = COUNTERS.snapshot()
    spans = Spans()
    worker, observer = ServeWorker(engine), TokenObserver()
    worker.start()
    observer.start()
    setup_s = time.perf_counter() - cell.t_start

    # the window: send for `seconds`
    clock = time.perf_counter
    trace_at = cell.seconds - w["trace"]["seconds"] if cell.trace else None
    trace_dir, trace_path = os.path.join(cell.scratch, "trace"), None
    window = contextlib.ExitStack()
    reqs, due, sent = [], [], []
    t0 = clock()
    try:
        for t_due, prompt, max_new in timeline:
            if trace_at is not None and t_due >= trace_at:
                time.sleep(max(0.0, t0 + trace_at - clock()))
                start_trace(trace_dir)
                window.enter_context(
                    jax.profiler.TraceAnnotation("bench.window"))
                trace_at = None
            delay = t0 + t_due - clock()
            if delay > 0:
                time.sleep(delay)
            due.append(t0 + t_due)
            sent.append(clock())
            with spans.span("bench.submit"):
                reqs.append(engine.submit(prompt, max_new))
            observer.watch(reqs[-1])
        time.sleep(max(0.0, t0 + cell.seconds - clock()))
        window.close()
        if cell.trace:
            trace_path = stop_trace(trace_dir)
        t_end = t0 + cell.seconds
        while engine.has_work() and worker.is_alive() \
                and clock() < t_end + w["drain_seconds"]:
            time.sleep(0.005)
        drained_s = clock() - t_end
    finally:
        window.close()
        worker.stop()
        observer.stop()
    peak = peak_bytes(cell.devices)
    counters = COUNTERS.delta_since(counters_before)
    compiles_in_window = len(cell.compiles) - compiled_before
    program_spans = recorder.last_events() if recorder else []
    if recorder:
        recorder.close()

    finished = [r.state == "finished" for r in reqs]
    stamps = observer.times
    ttft = due_latencies_ms(
        due, [ts[0] if ok and ts else None
              for ts, ok in zip(stamps, finished)], cell.seconds)
    gaps = token_gaps_ms(stamps)
    in_window = sum(1 for ts in stamps for t in ts if t <= t_end)
    late = [s - d for s, d in zip(sent, due)]
    end_to_end = {"serve_ttft_p95_ms": percentile(ttft, 95),
                  "serve_itl_p95_ms": percentile(gaps, 95),
                  "serve_tokens_per_s": in_window / cell.seconds,
                  "setup_s": setup_s}

    ok, check_note = _check_outputs(cell, model, params, reqs,
                                    w["check"])
    engine.close()
    notes = [{"requests": len(reqs), "finished": sum(finished),
              "rate_rps": traffic["rate_rps"],
              "ttft_ms_median": percentile(ttft, 50),
              "ttft_ms_mean": sum(ttft) / len(ttft),
              "itl_ms_median": percentile(gaps, 50),
              "itl_ms_max": max(gaps, default=None),
              "observer_late_ms_max": 1e3 * observer.late_max,
              "output_tokens": sum(len(r.out) for r in reqs),
              "generator_late_ms_mean": 1e3 * sum(late) / len(late),
              "generator_late_ms_max": 1e3 * max(late),
              "drained_s": drained_s,
              "backlog_at_end_of_sending":
              _backlog(sent, observer.finished, t_end),
              "backlog_at_middle":
              _backlog(sent, observer.finished, t0 + cell.seconds / 2),
              "compiles_in_window": compiles_in_window,
              "engine_steps": engine.steps,
              "peak_blocks_in_use": engine.peak_blocks_in_use,
              "kv_capacity_blocks": engine.kv.capacity_blocks},
             check_note]
    return RunResult(
        end_to_end=end_to_end, correct=ok and compiles_in_window == 0,
        attempted=len(reqs), failed=len(reqs) - sum(finished), notes=notes,
        memory_peak_bytes=peak, host_spans=spans.seconds, counters=counters,
        program_spans=program_spans, trace_path=trace_path)


def _backlog(sent, finished, t) -> int:
    """Requests submitted by `t` and not seen finished by `t`."""
    return sum(1 for s, f in zip(sent, finished)
               if s <= t and (f is None or f > t))
