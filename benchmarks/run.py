"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children: the process that touches JAX holds the chip.
This file knows no cell, model or metric by name.  It finds them from
`BENCHMARK.json` and the data files beside it (benchmarks/README.md):

    workloads/<cell>.json        the cell: runner, engine settings, checks
    configs/<config>.json        the model configuration, names its family
    traffic/<traffic>.json       the traffic mix, names its generator
    layer_metrics/<metric>.json  a per-layer metric, names its reader

It refuses to run (exit code 2, no result line) unless JAX finds TPUs, as
many as the cell asks for, of a kind listed in peaks.json: there is no CPU
mode and no default peak.  The last line of stdout is the one JSON object
of the contract, with what the check compared beside its limits under
`check`, its last key (and as stderr's last line); everything else (MFU,
how late the generator ran) is printed on earlier lines as `# <json>`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can see it

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import Cell, load_json, plugin  # noqa: E402

SCRATCH = os.path.join(ROOT, ".bench_tmp")  # traces and span files; git-ignored
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot give a device number; no result line is printed."""


def note(**fields) -> None:
    """A line for the reader and for PERF.md; never the last line."""
    print("# " + json.dumps(fields), flush=True)


def metrics_of(benchmark: dict, section: str, cell: str):
    """The metrics of `section` this cell reports: those with no
    `workloads` key, and those that list the cell."""
    return [m for m in benchmark[section]
            if "workloads" not in m or cell in m["workloads"]]


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform!r} "
                      f"({len(devices)} device(s))")
    if len(devices) != chips:
        raise Refused(f"the cell asks for {chips} chip(s); JAX found "
                      f"{len(devices)}")
    peaks = load_json("peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in peaks.json; a device "
                      f"without published peaks is an error, not a default")
    return devices, peaks[kind]


def build_cell(args, benchmark: dict) -> Cell:
    entry = next((w for w in benchmark["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise Refused(f"BENCHMARK.json has no workload {args.workload!r}")
    devices, peaks = check_devices(entry["chips"])

    import logging

    import jax
    import jax.monitoring

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    for handler in logging.getLogger("deepspeed_tpu").handlers:
        handler.setStream(sys.stderr)  # stdout is for the result and notes

    cache_dir = enable_compile_cache()
    # also keep the programs that compile in under a second (the engine
    # builds some twenty of them): a second run then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name == COMPILE_EVENT else None)

    workload = load_json("workloads", entry["name"] + ".json")
    config = load_json("configs", entry["config"] + ".json")
    traffic = load_json("traffic", entry["traffic"] + ".json")
    os.makedirs(SCRATCH, exist_ok=True)
    note(workload=entry["name"], config=entry["config"],
         traffic=entry["traffic"], chips=entry["chips"], seed=args.seed,
         seconds=args.seconds, trace=args.trace, compile_cache=cache_dir)
    return Cell(name=entry["name"], chips=entry["chips"], seed=args.seed,
                seconds=float(args.seconds), trace=bool(args.trace),
                workload=workload, config=config, traffic=traffic,
                family=plugin("models", config["family"]),
                generator=plugin("traffic", traffic["generator"]),
                peaks=peaks, devices=devices, scratch=SCRATCH,
                t_start=T_START, compiles=compiles)


def read_layer_metrics(cell: Cell, wanted, run):
    """Each per-layer metric through the reader its own file names.  A
    reader that finds nothing returns None and the metric is left out.
    -> (metrics, reduced trace or None)."""
    from benchmarks import trace_reduce

    trace = (trace_reduce.reduce_file(run.trace_path)
             if run.trace_path else None)
    out = {}
    for m in wanted:
        spec = load_json("layer_metrics", m["name"] + ".json")
        reader = plugin("readers", spec["reader"])
        value = reader.read(cell=cell, run=run, trace=trace,
                            **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out, trace


def device_block(cell: Cell, run, trace) -> dict:
    d0 = cell.devices[0]
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(cell.devices),
           "memory_peak_bytes": int(run.memory_peak_bytes)}
    if trace is not None:
        out["busy_s"] = trace.busy_s
        out["window_s"] = trace.window_s
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    try:
        cell = build_cell(args, benchmark)
    except Refused as e:
        print(f"benchmarks/run.py: refused: {e}", file=sys.stderr)
        return 2

    run = plugin("runners", cell.workload["runner"]).run(cell)
    for line in run.notes:
        note(**line)

    trace = None
    if cell.trace:
        wanted = metrics_of(benchmark, "per_layer", cell.name)
        metrics, trace = read_layer_metrics(cell, wanted, run)
    else:
        wanted = metrics_of(benchmark, "end_to_end", cell.name)
        metrics = {m["name"]: {"value": float(run.end_to_end[m["name"]]),
                               "unit": m["unit"]} for m in wanted}
    result = {"correct": bool(run.correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": device_block(cell, run, trace)}
    if trace is not None:
        result["breakdown"] = trace.breakdown()
    # what the check compared, each number beside its limit (a runner's
    # last note), and the compiles the window held: the line's last key
    # and stderr's last line
    result["check"] = dict(run.notes[-1], **{
        "compiles_in_window": n["compiles_in_window"]
        for n in run.notes[:-1] if "compiles_in_window" in n})
    print("benchmarks/run.py: " + ("compared" if run.correct else "incorrect")
          + ": " + json.dumps(result["check"]), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
