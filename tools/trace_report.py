#!/usr/bin/env python
"""Merge per-rank trace timelines into Chrome/Perfetto trace-event JSON.

Input: a run directory holding `trace.rank*.jsonl` files written by
`monitor.tracing.TraceRecorder` (enabled via
`"monitor": {"tracing": {"enabled": true}}`, or `serve_bench --trace`).
Output: one trace-event JSON (object format, `traceEvents` array) that
chrome://tracing and https://ui.perfetto.dev load directly —
pid = rank, tid = subsystem lane (train/input/wire/ckpt/autotune/
watchdog/serve/slo), with process/thread name metadata events.

Clock-skew alignment: each rank's recorder captures its
(wall, monotonic) clock pair right after a collective allgather at
init — an approximately simultaneous instant on every rank — so the
merger pins every FIRST segment's sync instant to the same merged
timestamp instead of trusting wall clocks across hosts.  Later
segments of the same rank (a restarted process appends a fresh
`trace_meta`) are placed by their wall-clock delta from that rank's
first segment — same host, same wall.  Lanes from DIFFERENT run dirs
(e.g. a training run beside a serving run) are each shifted to start
at 0 and stacked by pid block.

With `--xplane FILE` (a profiler capture of the same run, `.xplane.pb`
or `.xplane.pb.gz`) it prints instead, for each program that left a
`program_scopes` event in the run dir's trace file, device ms a run by
stage and by every scope beneath one: the join of the program's own map
with the device's own events (`monitor/tracing.py::device_scope_times`;
docs/tutorials/tracing.md, "Device time by stage").

Usage:
    python tools/trace_report.py RUN_DIR [RUN_DIR2 ...] [-o out.json]
    python tools/trace_report.py RUN_DIR --xplane FILE
    python tools/trace_report.py --selftest
    python tools/trace_report.py --campaign   # the committed 2-lane
        # artifact: a 2-process training lane (overlapped wire -> real
        # exposed-wire waits on both ranks) + the serve_bench traced
        # Poisson lane, merged into one Perfetto file
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import socket
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

# one pid block per run dir so two lanes never collide on rank numbers
PID_STRIDE = 100


def load_rank_traces(run_dir):
    """{rank: (segments, summary)} for every trace.rank*.jsonl."""
    from deepspeed_tpu.monitor.tracing import (TRACE_FILE_PREFIX,
                                               read_trace_file)

    out = {}
    pattern = os.path.join(run_dir, f"{TRACE_FILE_PREFIX}*.jsonl")
    for path in sorted(glob.glob(pattern)):
        base = os.path.basename(path)
        rank = int(base[len(TRACE_FILE_PREFIX):-len(".jsonl")])
        out[rank] = read_trace_file(path)
    if not out:
        raise FileNotFoundError(
            f"no {TRACE_FILE_PREFIX}*.jsonl under {run_dir!r} — is "
            f"monitor.tracing enabled?")
    return out


def _tid_of(cat, tids):
    if cat not in tids:
        tids[cat] = len(tids)
    return tids[cat]


def merge_dir(run_dir, pid_base=0, label=None, events=None, stats=None):
    """Append one run dir's aligned events onto `events` (Chrome trace
    array items).  Returns (min_ts_us, per-rank stats) — the caller
    applies the global zero-shift."""
    from deepspeed_tpu.monitor.tracing import TRACE_CATEGORIES

    label = label or os.path.basename(os.path.normpath(run_dir))
    events = events if events is not None else []
    min_ts = None
    for rank, (segments, summary) in sorted(
            load_rank_traces(run_dir).items()):
        pid = pid_base + rank
        tids = {cat: i for i, cat in enumerate(TRACE_CATEGORIES)}
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": f"{label} rank {rank}"}})
        named_tids = set()
        first_meta = segments[0][0] if segments else None
        n_events = 0
        for meta, segment_events in segments:
            # first segment: origin at the sync instant (collective-
            # simultaneous across ranks); later segments (process
            # restarts): placed by wall delta from the first segment
            offset_us = 0
            if first_meta is not None and meta is not first_meta:
                offset_us = int((meta.get("sync_wall", 0.0)
                                 - first_meta.get("sync_wall", 0.0))
                                * 1e6)
            sync_mono = int(meta.get("sync_mono_us", 0))
            for e in segment_events:
                cat = e.get("cat", "train")
                tid = _tid_of(cat, tids)
                if tid not in named_tids:
                    named_tids.add(tid)
                    events.append(
                        {"ph": "M", "pid": pid, "tid": tid,
                         "name": "thread_name", "args": {"name": cat}})
                ts = int(e["ts"]) - sync_mono + offset_us
                min_ts = ts if min_ts is None else min(min_ts, ts)
                out = {"ph": e["ph"], "name": e["name"], "cat": cat,
                       "pid": pid, "tid": tid, "ts": ts}
                if e["ph"] == "X":
                    out["dur"] = int(e.get("dur", 0))
                else:
                    out["s"] = "p"  # instant scoped to the process row
                if e.get("args"):
                    out["args"] = e["args"]
                events.append(out)
                n_events += 1
        if stats is not None:
            stats[f"{label}/rank{rank}"] = {
                "events": n_events,
                "segments": len(segments),
                "skew_est_s": (first_meta or {}).get("skew_est_s"),
                "dropped": (summary or {}).get("dropped"),
            }
    return events, min_ts


def merge_runs(run_dirs, labels=None):
    """Merge one or more run dirs into a Chrome trace-event object.
    Each dir gets its own pid block and its own zero origin (lanes are
    stacked for side-by-side reading, not wall-aligned across dirs)."""
    all_events = []
    stats = {}
    for i, run_dir in enumerate(run_dirs):
        label = labels[i] if labels else None
        dir_events, min_ts = merge_dir(run_dir, pid_base=i * PID_STRIDE,
                                       label=label, stats=stats)
        shift = -(min_ts or 0)
        for e in dir_events:
            if "ts" in e:
                e["ts"] += shift
        all_events.extend(dir_events)
    return {"traceEvents": all_events, "displayTimeUnit": "ms",
            "otherData": {"tool": "deepspeed_tpu tools/trace_report.py",
                          "ranks": stats}}


def prefill_skips(merged):
    """{(pid, rid): {"cached", "computed"}} — the prefix-cache outcome
    per request, from the cached/computed token counts the serving
    engine stamps on every `prefill_chunk` span (serving/engine.py):
    how many prompt tokens this request never prefilled because their
    KV blocks were already resident."""
    out = {}
    for e in merged["traceEvents"]:
        if e.get("ph") == "M" or e.get("name") != "prefill_chunk":
            continue
        args = e.get("args") or {}
        if "rid" not in args:
            continue
        out[(e["pid"], args["rid"])] = {
            "cached": int(args.get("cached", 0)),
            "computed": int(args.get("computed", 0))}
    return out


def scope_report(run_dir, xplane):
    """The lines `--xplane` prints: device time by the serving
    programs' stages for every program whose `program_scopes` event any
    rank's newest segment in `run_dir` holds, over the profile at
    `xplane`."""
    from deepspeed_tpu.monitor.tracing import (device_scope_times,
                                               load_profile, scope_table)
    from deepspeed_tpu.serving.programs import STAGES

    events = [e for segments, _ in load_rank_traces(run_dir).values()
              for e in segments[-1][1]]
    times = device_scope_times(load_profile(xplane), events)
    if not times:
        return [f"no program_scopes event under {run_dir!r}: attach the "
                f"recorder with engine.attach_tracing(tracer=...)"]
    return scope_table(times, STAGES)


def write_merged(run_dirs, out_path, labels=None):
    merged = merge_runs(run_dirs, labels=labels)
    skips = prefill_skips(merged)
    if skips:
        merged["otherData"]["prefill_skips"] = {
            f"pid{pid}/rid{rid}": s
            for (pid, rid), s in sorted(skips.items())}
    with open(out_path, "w") as f:
        json.dump(merged, f)
    n = sum(1 for e in merged["traceEvents"] if e["ph"] != "M")
    print(f"wrote {out_path}: {n} events from "
          f"{len(merged['otherData']['ranks'])} rank timeline(s) — "
          f"load in chrome://tracing or https://ui.perfetto.dev")
    if skips:
        cached = sum(s["cached"] for s in skips.values())
        computed = sum(s["computed"] for s in skips.values())
        hit = sum(1 for s in skips.values() if s["cached"])
        print(f"prefix cache: {hit}/{len(skips)} request(s) skipped "
              f"cached prefill — {cached:,} prompt token(s) served "
              f"from cache, {computed:,} computed")
        for (pid, rid), s in sorted(skips.items()):
            if s["cached"]:
                print(f"    pid {pid} rid {rid}: {s['cached']} cached "
                      f"+ {s['computed']} computed")
    return merged


# -- selftest ---------------------------------------------------------------


def selftest() -> int:
    """Deterministic two-rank round-trip with INJECTED skewed clocks:
    rank 1's monotonic clock reads 7.5 s ahead of rank 0's, both sync
    at the same true instant, and events recorded at the same true
    time must land at the same merged timestamp.  Plus a restart
    segment placed by wall delta, and slo/meta hygiene."""
    import tempfile

    from deepspeed_tpu.monitor.tracing import TraceRecorder

    class Clocks:
        """One true time driving two skewed (mono, wall) clock pairs."""

        def __init__(self, mono_skew_s, wall_skew_s):
            self.t = 0.0
            self.mono_skew = mono_skew_s
            self.wall_skew = wall_skew_s

        def mono(self):
            return self.t + self.mono_skew

        def wall(self):
            return 1_000_000.0 + self.t + self.wall_skew

    with tempfile.TemporaryDirectory() as tmp:
        c0 = Clocks(0.1, 0.0)
        c1 = Clocks(7.5, 0.25)  # mono AND wall skew vs rank 0
        # both recorders constructed at true t=0: their sync instants
        # are simultaneous, like the post-allgather capture in a run
        r0 = TraceRecorder(tmp, rank=0, world=2, clock=c0.mono,
                           wall=c0.wall, flush_interval_s=10)
        r1 = TraceRecorder(tmp, rank=1, world=2, clock=c1.mono,
                           wall=c1.wall, flush_interval_s=10)
        c0.t = c1.t = 1.0  # one true second later, on both ranks
        r0.add_complete("apply", "train", ts_us=r0.now_us(),
                        dur_us=2000, step=3)
        r1.add_complete("apply", "train", ts_us=r1.now_us(),
                        dur_us=2000, step=3)
        c0.t = c1.t = 1.5
        r0.instant("watchdog_beat", "watchdog", step=3)
        r1.add_complete("wire_exposed", "wire", dur_us=800, step=4)
        # a serving prefill span carrying the prefix-cache outcome
        # (engine stamps cached/computed on every prefill_chunk)
        r1.add_complete("prefill_chunk", "serve", dur_us=500, rid=7,
                        pos=0, n=4, cached=12, computed=4)
        r0.close()
        r1.close()
        # rank 0 restarts 100 true seconds later: a second recorder
        # appends a fresh segment to the same file, fresh mono origin
        c0r = Clocks(0.0, 0.0)
        c0r.t = 100.0
        r0b = TraceRecorder(tmp, rank=0, world=2, clock=c0r.mono,
                            wall=c0r.wall, flush_interval_s=10)
        c0r.t = 101.0
        r0b.instant("autotune.retune", "autotune", reason="selftest")
        r0b.close()

        merged = merge_runs([tmp], labels=["train"])
        evs = merged["traceEvents"]
        data = [e for e in evs if e["ph"] != "M"]
        meta = [e for e in evs if e["ph"] == "M"]
        # pid = rank; process/thread names present
        assert {e["pid"] for e in data} == {0, 1}, data
        pnames = {e["args"]["name"] for e in meta
                  if e["name"] == "process_name"}
        assert pnames == {"train rank 0", "train rank 1"}, pnames
        tnames = {e["args"]["name"] for e in meta
                  if e["name"] == "thread_name"}
        assert {"train", "wire", "watchdog", "autotune"} <= tnames, tnames
        # the skew cancels: same-true-instant events align exactly
        applies = {e["pid"]: e["ts"] for e in data
                   if e["name"] == "apply"}
        assert applies[0] == applies[1], applies
        beat = next(e for e in data if e["name"] == "watchdog_beat")
        wire = next(e for e in data if e["name"] == "wire_exposed")
        # wire_exposed is back-dated by its 800 µs duration
        assert beat["ts"] - (wire["ts"] + wire["dur"]) == 0, (beat, wire)
        assert wire["tid"] != beat["tid"], "categories get their own tid"
        # zero origin at the sync instant; everything non-negative
        assert min(e["ts"] for e in data) == 0, min(
            e["ts"] for e in data)
        # the restart segment landed exactly 100 true seconds after the
        # apply spans via the wall delta (exact with injected clocks)
        ret = next(e for e in data if e["name"] == "autotune.retune")
        assert ret["ts"] - applies[0] == 100_000_000, (ret, applies)
        assert ret["s"] == "p", ret
        # args survive the merge
        assert next(e for e in data
                    if e["name"] == "apply")["args"]["step"] == 3
        # the per-request prefix-cache skip is recoverable from the
        # merged stream (the trace-side view of kv.prefix_hit_tokens)
        assert prefill_skips(merged) == {
            (1, 7): {"cached": 12, "computed": 4}}, \
            prefill_skips(merged)
        # the file round-trips through json and is self-describing
        blob = json.dumps(merged)
        back = json.loads(blob)
        assert back["traceEvents"] and back["displayTimeUnit"] == "ms"
        st = merged["otherData"]["ranks"]
        assert st["train/rank0"]["segments"] == 2, st
        assert st["train/rank0"]["dropped"] == 0, st
    print("trace_report selftest ok")
    return 0


# -- the 2-lane campaign ----------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def train_worker(args) -> int:
    """One rank of the 2-process training lane: a nano GPT data-
    parallel engine with the OVERLAPPED bucketed wire (gas=2, so micro
    N's exchange hides behind micro N+1's compute and the per-step
    drain leaves a real `wire_exposed` wait on the timeline) and
    tracing enabled — both ranks write trace.rank*.jsonl into the
    shared run dir, clock-synced over the distributed KV store."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=args.coord,
                               num_processes=args.nproc,
                               process_id=args.proc_id)
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config

    dp = jax.device_count()
    model_cfg = gpt2_config("nano", vocab_size=256, max_seq_len=32,
                            dropout=0.0, embed_dropout=0.0)
    gas = 2
    cfg = {
        "train_batch_size": dp * gas,
        "train_micro_batch_size_per_gpu": 1,
        "mesh": {"data": dp},
        "steps_per_print": 0,
        "optimizer": {"type": "Adam",
                      "params": {"lr": 1e-4, "weight_decay": 0.0}},
        "comm": {"gradient_reduction": "bucketed", "wire_dtype": "int8",
                 "overlap": "on"},
        "monitor": {"enabled": True, "output_path": args.out,
                    "job_name": "train", "flush_interval": 1,
                    "tracing": {"enabled": True,
                                "flush_interval_s": 0.1}},
    }
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT(model_cfg), dist_init_required=False,
        config_params=cfg)
    assert "grads" in engine._step_fns, "overlapped wire did not engage"
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 256, (dp, 33)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])
    for _ in range(args.steps):
        for _m in range(gas):
            engine.forward(batch)
            engine.backward()
        engine.step()
    engine.finalize_monitoring()
    return 0


def run_training_lane(out_dir, steps=4, nproc=2, timeout_s=600):
    """Spawn the 2-process TCP training lane writing into out_dir."""
    coord = f"127.0.0.1:{_free_port()}"
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--train-worker",
         "--proc-id", str(pid), "--coord", coord, "--nproc", str(nproc),
         "--steps", str(steps), "--out", out_dir],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),  # CPU lane
        stdout=subprocess.DEVNULL if pid else None)
        for pid in range(nproc)]
    for p in procs:
        rc = p.wait(timeout=timeout_s)
        assert rc == 0, f"training-lane worker exited {rc}"


def run_campaign(steps=4, record=True):
    """The committed 2-lane trace artifact: (1) the 2-process training
    lane above — two ranks, overlapped int8 wire, exposed-wire waits
    and dispatch spans on both timelines; (2) the serve_bench traced
    Poisson lane — per-request serving lifecycle + SLO windows whose
    p50/p99 TTFT the bench itself asserts against its own table.  Both
    merge into one Perfetto file; run_report renders the serving run's
    "Serving SLO" section."""
    import serve_bench

    from deepspeed_tpu.monitor.artifacts import record_bench_result
    from deepspeed_tpu.monitor.tracing import TRACE_FILE_PREFIX

    root = os.path.join(os.path.dirname(HERE), "bench_artifacts", "runs")
    print("--- lane: 2-process training (overlapped int8 wire) ---")
    import tempfile

    train_tmp = tempfile.mkdtemp(prefix="trace_train_")
    run_training_lane(train_tmp, steps=steps)
    train_dir = os.path.join(train_tmp, "train")
    ranks = sorted(glob.glob(os.path.join(
        train_dir, f"{TRACE_FILE_PREFIX}*.jsonl")))
    assert len(ranks) == 2, f"expected 2 rank traces, got {ranks}"

    print("--- lane: traced serving Poisson (serve_bench) ---")
    serve = serve_bench.run_campaign(record=False, dry=True, trace=True)
    serve_tmp = serve["trace"]["dir"]

    merged = merge_runs([train_dir, serve_tmp],
                        labels=["train", "serve"])
    data = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    names = {(e["pid"], e["name"]) for e in data}
    for rank in (0, 1):
        assert (rank, "wire_exposed") in names, \
            f"rank {rank} shows no exposed-wire wait"
        assert (rank, "dispatch.micro") in names or \
            (rank, "dispatch.grads") in names, names
    assert (PID_STRIDE, "queue_wait") in names, "serving lane missing"
    assert (PID_STRIDE, "decode_step") in names

    result = {
        "metric": "trace_timelines",
        "platform": "cpu",
        "lanes": {
            "train_2proc": {"ranks": 2, "steps": steps,
                            "events": sum(
                                1 for e in data if e["pid"] < PID_STRIDE)},
            "serve_poisson": {
                "requests": serve["lanes"]["continuous"]["requests"],
                "events": sum(
                    1 for e in data if e["pid"] >= PID_STRIDE),
                "slo": serve["trace"]["slo"]},
        },
        "value": len(data),
        "unit": "merged trace events",
    }
    if record:
        result["artifact"] = record_bench_result(result)
        stamp = os.path.basename(result["artifact"]).rsplit(".", 1)[0]
        run_dir = os.path.join(root, stamp)
        os.makedirs(run_dir, exist_ok=True)
        import shutil

        # train lane: rank traces + telemetry events; serve lane: the
        # serve_bench trace + slo events + its lane table
        for sub, src in (("train", train_dir), ("serve", serve_tmp)):
            dst = os.path.join(run_dir, sub)
            os.makedirs(dst, exist_ok=True)
            for path in glob.glob(os.path.join(src, "*.jsonl")) + \
                    glob.glob(os.path.join(src, "*.json")):
                shutil.copy(path, dst)
        with open(os.path.join(run_dir, "serve", "events.rank00000"
                               ".jsonl"), "w") as f:
            for ev in serve["trace"]["slo_events"]:
                f.write(json.dumps(ev) + "\n")
        serving = {"schema_version": serve_bench.SERVING_SCHEMA_VERSION,
                   "model": serve["model"],
                   "n_requests": serve["n_requests"],
                   "rate_hz": serve["rate_hz"],
                   "lanes": {name: {k: v for k, v in lane.items()
                                    if k not in ("counters", "outputs")}
                             for name, lane in serve["lanes"].items()}}
        with open(os.path.join(run_dir, "serve", "serving.json"),
                  "w") as f:
            json.dump(serving, f, indent=2, sort_keys=True)
        out_path = os.path.join(run_dir, "trace.merged.json")
        with open(out_path, "w") as f:
            json.dump(merged, f)
        result["run_dir"] = os.path.relpath(run_dir,
                                            os.path.dirname(HERE))
        print(f"artifact: {result['artifact']}")
        print(f"merged:   {os.path.relpath(out_path, os.path.dirname(HERE))}")
        print(f"report:   python tools/run_report.py "
              f"{result['run_dir']}/serve")
    import shutil

    shutil.rmtree(train_tmp, ignore_errors=True)
    shutil.rmtree(serve_tmp, ignore_errors=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dirs", nargs="*",
                    help="run dir(s) holding trace.rank*.jsonl")
    ap.add_argument("-o", "--output",
                    help="merged JSON path (default: trace.merged.json "
                    "in the first run dir)")
    ap.add_argument("--selftest", action="store_true",
                    help="deterministic skewed-clock round-trip")
    ap.add_argument("--campaign", action="store_true",
                    help="record the 2-lane (training x serving) "
                    "trace artifact")
    ap.add_argument("--xplane",
                    help="a profiler capture of the run: print device "
                    "time by stage for each program of the run dir's "
                    "program_scopes events instead of merging")
    ap.add_argument("--no-record", action="store_true")
    ap.add_argument("--steps", type=int, default=4)
    # train-worker plumbing (run_training_lane spawns these)
    ap.add_argument("--train-worker", dest="train_worker",
                    action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--proc-id", dest="proc_id", type=int, default=0)
    ap.add_argument("--coord", default="")
    ap.add_argument("--nproc", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.train_worker:
        return train_worker(args)
    if args.selftest:
        return selftest()
    if args.campaign:
        run_campaign(steps=args.steps, record=not args.no_record)
        return 0
    if not args.run_dirs:
        ap.error("run_dirs required (or --selftest / --campaign)")
    if args.xplane:
        print("\n".join(scope_report(args.run_dirs[0], args.xplane)))
        return 0
    out = args.output or os.path.join(args.run_dirs[0],
                                      "trace.merged.json")
    write_merged(args.run_dirs, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
