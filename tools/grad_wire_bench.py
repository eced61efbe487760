"""Gradient-wire bench: unfused implicit psum vs the bucketed wire.

Measures the dense data-parallel engine step through every wire the
engine offers (runtime/comm/bucketing.py):

  unfused        implicit XLA psum at the loss-mean boundary — one
                 collective per grad leaf (~40 for gpt2-nano)
  bucketed       BucketPlan fp32 allreduce — one fused collective per
                 dtype bucket
  bucketed_bf16  same buckets, bf16 on the wire (half the bytes)
  bucketed_split same buckets, the EleutherAI 24-bit frexp wire
                 (fp16 mantissa + int8 exponent all-gathers)
  bucketed_int8  same buckets, blockwise int8 + fp16 scales (the qgZ
                 compression half, comm/quant.py)
  zero2 / zero2_bucketed   the ZeRO-2 lane: implicit vs the bucketed
                 reduce-scatter lowering

Two fabrics:

  --nproc 1  (default) single-process CPU mesh — collectives are memory
             movement; shows the bucketing overhead floor.
  --nproc N  N jax.distributed processes on localhost (gloo/TCP): every
             cross-process payload pays a real byte-proportional
             serialize/send cost — the fabric where round-5 measured the
             dense step at 270 ms vs 53 ms for the fused onebit wire.

--hierarchy adds the two-level lanes (comm.hierarchy, ZeRO++-style):
processes map to outer groups (data_outer = nproc on the TCP fabric, 2
on the single-process mesh), so only the 1/inner-size shard crosses the
slow boundary per bucket:

  hier             fp32 both levels (exact; parity with `bucketed`)
  hier_outer_bf16  slow hop compressed to bf16, fast hop exact
  hier_outer_split slow hop on the 24-bit frexp gather
  hier_outer_int8  slow hop on blockwise int8 + fp16 scales (qgZ)
  hier_outer_int4  slow hop on packed int4 nibbles + fp16 scales
  zero2_hier       hierarchical reduce-scatter + hpZ secondary shards
                   (post-step param gather stays intra-group)
  zero2_hier_int8  same + the quantized slow hop

Each hier row reports the measured grad_wire.intra / grad_wire.inter
counter split beside the plan prediction, and the pad-free logical
payload so bucket padding never masks a compression win.

Results are recorded through monitor/artifacts.py into
bench_artifacts/runs/ + manifest (the PR-2 durable-artifact rule).

Usage: python tools/grad_wire_bench.py [--nproc 2] [--steps 20]
           [--size nano] [--seq 32] [--hierarchy]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

VARIANTS = [
    ("unfused", 0, None),
    ("bucketed", 0, {"gradient_reduction": "bucketed"}),
    ("bucketed_bf16", 0, {"gradient_reduction": "bucketed",
                          "wire_dtype": "bf16"}),
    ("bucketed_split", 0, {"gradient_reduction": "bucketed",
                           "wire_dtype": "split"}),
    ("bucketed_int8", 0, {"gradient_reduction": "bucketed",
                          "wire_dtype": "int8"}),
    ("zero2", 2, None),
    ("zero2_bucketed", 2, {"gradient_reduction": "bucketed"}),
]


def overlap_variants(outer: int, gas: int = 2):
    """--overlap lanes: serial/overlapped pairs over the same wires
    (comm.overlap rides the host exchange — runtime/comm/overlap.py).
    gas>1 so micro N's exchange hides behind micro N+1's compute; the
    serial twin runs the same composition for a like-for-like step.
    Parity contract: the int8 lanes and the outer=2 hierarchical lanes
    are BIT-identical serial-vs-overlap by construction (gather wires
    share the sum expression; a 2-element reduce is commutative); the
    flat bf16 pair matches within cross-process reduction-order
    rounding (gloo's ring rotates chunk association — measured)."""
    flat = {"gradient_reduction": "bucketed"}
    hier = dict(flat, hierarchy={"outer": outer})
    lanes = []
    for name, base, wire in (
            ("flat_bf16", flat, "bf16"), ("flat_int8", flat, "int8"),
            ("hier_bf16", hier, "bf16"), ("hier_int8", hier, "int8")):
        key = "wire_dtype" if base is flat else "wire_dtype_outer"
        comm = dict(base, **{key: wire})
        lanes.append((f"{name}_serial", 0, dict(comm, overlap="none"),
                      {"gas": gas}))
        lanes.append((f"{name}_overlap", 0, dict(comm, overlap="on"),
                      {"gas": gas}))
    return lanes


def hier_variants(outer: int):
    """--hierarchy lanes: two-level reduction with data_outer groups."""
    base = {"gradient_reduction": "bucketed", "hierarchy": {"outer": outer}}
    return [
        ("hier", 0, dict(base)),
        ("hier_outer_bf16", 0, dict(base, wire_dtype_outer="bf16")),
        ("hier_outer_split", 0, dict(base, wire_dtype_outer="split")),
        ("hier_outer_int8", 0, dict(base, wire_dtype_outer="int8")),
        ("hier_outer_int4", 0, dict(base, wire_dtype_outer="int4")),
        ("zero2_hier", 2, dict(base)),
        ("zero2_hier_int8", 2, dict(base, wire_dtype_outer="int8")),
    ]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def measure_variants(variants, steps: int, size: str, seq: int,
                     warmup: int = 5):
    """Run each (name, stage, comm-config) lane through the engine and
    return ({name: entry}, n_params) — shared by the TCP/CPU bench
    paths and the tier-1 dry-run."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config
    from deepspeed_tpu.monitor.counters import COUNTERS

    dp = jax.device_count()
    model_cfg = gpt2_config(size, vocab_size=512,
                            max_seq_len=seq, dropout=0.0,
                            embed_dropout=0.0)
    n_params = GPT(model_cfg).num_params()
    rng = np.random.RandomState(0)  # identical stream on every process
    tok = rng.randint(0, 512, (dp, seq + 1)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])

    results = {}
    for variant in variants:
        name, stage, comm = variant[:3]
        opts = variant[3] if len(variant) > 3 else {}
        gas = int(opts.get("gas", 1))
        cfg = {
            "train_batch_size": dp * gas,
            "zero_optimization": {"stage": stage},
            "mesh": {"data": dp},
            "steps_per_print": 0,
            "optimizer": {"type": "Adam",
                          "params": {"lr": 1e-4, "weight_decay": 0.0}},
        }
        if gas > 1:
            # the same (dp, seq) token block feeds every micro step:
            # micro batch stays 1 row/rank, the step runs gas micros
            cfg["train_micro_batch_size_per_gpu"] = 1
        if comm is not None:
            cfg["comm"] = comm
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT(model_cfg), dist_init_required=False,
            config_params=cfg)
        if comm is not None:
            assert engine.bucket_plan is not None, \
                f"{name}: bucketed wire did not engage"
        if comm is not None and comm.get("overlap") in ("on", "auto"):
            assert "grads" in engine._step_fns, \
                f"{name}: overlapped wire did not engage"
        for _ in range(warmup):  # compile + warm
            for _m in range(gas):
                engine.forward(batch)
                engine.backward()
            engine.step()
        snap = COUNTERS.snapshot()
        t = []
        for _ in range(steps):
            t0 = time.perf_counter()
            for _m in range(gas):
                loss = engine.forward(batch)
                engine.backward()
            engine.step()
            loss.block_until_ready()
            t.append(time.perf_counter() - t0)
        entry = {"step_ms": round(float(np.median(t)) * 1e3, 2),
                 "loss": float(loss), "gas": gas}
        if engine.bucket_plan is not None:
            plan = engine.bucket_plan
            deltas = COUNTERS.delta_since(snap)
            wire = deltas.get("grad_wire.reduce", {})
            entry.update({
                "n_buckets": plan.n_buckets,
                "wire": plan.wire,
                "lowering": ("reduce-scatter" if plan.scatter
                             else "allreduce"),
                "wire_bytes_per_step": plan.wire_bytes_per_reduction,
                "logical_bytes_per_step":
                    plan.wire_bytes_logical_per_reduction,
                "collectives_per_step": plan.collectives_per_reduction,
                "counted_wire_bytes": int(wire.get("bytes", 0)),
            })
            if plan.quantized:
                entry["quant_block"] = plan.quant_block
            deltas_overlap = deltas.get("grad_wire.exposed_ms", {})
            if deltas_overlap:
                # µs-in-bytes convention (ckpt.stall_ms): the host wait
                # NOT hidden behind device compute, per drain
                entry["exposed_ms_per_step"] = round(
                    deltas_overlap.get("bytes", 0) / 1000.0
                    / max(1, deltas_overlap.get("calls", 1)), 3)
            if plan.hierarchical:
                inner, outer = plan.levels
                entry.update({
                    "wire": f"{inner.wire}/{outer.wire}",
                    "hierarchy": f"outer={outer.size} x inner={inner.size}",
                    "intra_bytes_per_step":
                        plan.wire_bytes_intra_per_reduction,
                    "inter_bytes_per_step":
                        plan.wire_bytes_inter_per_reduction,
                    "inter_logical_bytes_per_step":
                        plan.wire_bytes_inter_logical_per_reduction,
                    "counted_intra_bytes": int(deltas.get(
                        "grad_wire.intra", {}).get("bytes", 0)),
                    "counted_inter_bytes": int(deltas.get(
                        "grad_wire.inter", {}).get("bytes", 0)),
                    "counted_inter_logical_bytes": int(deltas.get(
                        "grad_wire.inter_logical", {}).get("bytes", 0)),
                })
        engine.close_overlap()
        results[name] = entry

    # overlap pairs: exposed-wire fraction + the parity contract.  Of
    # the serial lane's wire cost, how much is still on the critical
    # path with overlap on?  hidden = t_serial - t_overlap; exposed is
    # the measured blocked-on-the-wire host time.
    for name in list(results):
        if not name.endswith("_overlap"):
            continue
        serial = results.get(name[:-8] + "_serial")
        lane = results[name]
        if serial is None:
            continue
        exposed = lane.get("exposed_ms_per_step", 0.0)
        hidden = max(0.0, serial["step_ms"] - lane["step_ms"])
        lane["wire_hidden_ms_per_step"] = round(hidden, 2)
        lane["exposed_wire_frac"] = round(
            exposed / max(exposed + hidden, 1e-9), 4)
        lane["loss_bitwise_vs_serial"] = bool(
            np.float32(lane["loss"]) == np.float32(serial["loss"]))
        if "int8" in name or name.startswith("hier"):
            assert lane["loss_bitwise_vs_serial"], \
                (name, lane["loss"], serial["loss"])
    for entry in results.values():
        entry["loss"] = round(entry["loss"], 4)
    return results, n_params


def bench(args, nproc: int, proc_id: int):
    variants = list(VARIANTS)
    if args.hierarchy:
        # processes are the slow-fabric boundary on the TCP lane; the
        # single-process mesh has no real boundary — split it 2-ways so
        # the lowering still runs end-to-end (overhead floor)
        variants += hier_variants(nproc if nproc > 1 else 2)
    if args.overlap:
        variants += overlap_variants(nproc if nproc > 1 else 2,
                                     gas=args.overlap_gas)
    results, n_params = measure_variants(variants, args.steps, args.size,
                                         args.seq)

    if proc_id == 0:
        import jax

        dp = jax.device_count()
        base = results["unfused"]["step_ms"]
        for name in results:
            results[name]["vs_unfused"] = round(
                base / max(results[name]["step_ms"], 1e-9), 2)
        suffix = ("_overlap" if args.overlap
                  else "_hier" if args.hierarchy else "")
        # the headline value must track the metric the manifest row is
        # NAMED for: the exposed-wire fraction on --overlap runs, the
        # hierarchical lane on --hierarchy runs, else the flat bucketed
        if args.overlap:
            headline = results["hier_int8_overlap"]["exposed_wire_frac"]
            unit = "exposed_wire_frac_hier_int8"
        else:
            headline = results[
                "hier" if args.hierarchy else "bucketed"]["vs_unfused"]
            unit = "x_vs_unfused_dense"
        print(json.dumps({
            "metric": ("grad_wire_2proc_tcp" if nproc > 1
                       else "grad_wire_cpu_mesh") + suffix,
            "platform": "cpu",
            "n_params": int(n_params),
            "world": {"processes": nproc, "devices": dp},
            "steps": args.steps,
            "value": headline,
            "unit": unit,
            **results,
        }), flush=True)


def worker(args):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=args.coord,
                               num_processes=args.nproc,
                               process_id=args.proc_id)
    import deepspeed_tpu  # noqa: F401  (installs the gloo-collectives
    #                       flag BEFORE the CPU client exists)

    bench(args, args.nproc, args.proc_id)


def single_process(args):
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")
    bench(args, 1, 0)


def run_dry(artifact_root: str, steps: int = 2, size: str = "nano",
            seq: int = 16, outer: int = 2):
    """Tier-1 CPU dry-run of the QUANTIZED grad-wire lanes (the
    ckpt_bench/input_pipeline_bench pattern): runs in-process on the
    suite's virtual mesh so the qgZ path — quantized flat wire, int8/int4
    outer hops, counters, artifact recording — can never silently rot.
    Returns the recorded result dict."""
    variants = [
        ("unfused", 0, None),
        ("bucketed_int8", 0, {"gradient_reduction": "bucketed",
                              "wire_dtype": "int8"}),
    ] + [v for v in hier_variants(outer)
         if v[0] in ("hier_outer_int8", "hier_outer_int4",
                     "zero2_hier_int8")]
    results, n_params = measure_variants(variants, steps, size, seq,
                                         warmup=1)
    import jax

    from deepspeed_tpu.monitor.artifacts import record_bench_result

    result = {
        "metric": "grad_wire_cpu_mesh_quant_dryrun",
        "platform": "cpu",
        "n_params": int(n_params),
        "world": {"processes": 1, "devices": jax.device_count()},
        "steps": steps,
        "value": results["hier_outer_int8"]["inter_bytes_per_step"],
        "unit": "inter_bytes_per_step",
        **results,
    }
    result["artifact"] = record_bench_result(result, root=artifact_root)
    return result


def run_dry_overlap(artifact_root: str, steps: int = 2, size: str = "nano",
                    seq: int = 16, outer: int = 2, gas: int = 2):
    """Tier-1 CPU dry-run of the OVERLAP lanes (the run_dry pattern):
    runs the serial/overlapped pairs in-process on the suite's virtual
    mesh — grads/exchange/combine pipeline, exposed-wire counter,
    bit-identical losses, artifact recording — so comm.overlap can
    never silently rot.  On the single-process mesh EVERY pair is
    bitwise (the in-process psum is the ordered fold the combine
    mirrors); the assert below pins that."""
    variants = [v for v in overlap_variants(outer, gas=gas)
                if v[0].startswith(("flat_bf16", "hier_int8"))]
    results, n_params = measure_variants(variants, steps, size, seq,
                                         warmup=1)
    for name, entry in results.items():
        if name.endswith("_overlap"):
            assert entry["loss_bitwise_vs_serial"], (name, entry)
            assert "exposed_ms_per_step" in entry, name
    import jax

    from deepspeed_tpu.monitor.artifacts import record_bench_result

    result = {
        "metric": "grad_wire_cpu_mesh_overlap_dryrun",
        "platform": "cpu",
        "n_params": int(n_params),
        "world": {"processes": 1, "devices": jax.device_count()},
        "steps": steps,
        "value": results["hier_int8_overlap"]["exposed_wire_frac"],
        "unit": "exposed_wire_frac_hier_int8",
        **results,
    }
    result["artifact"] = record_bench_result(result, root=artifact_root)
    return result


def _record(out: str):
    """Durable artifact under bench_artifacts/runs/ (PR-2 rule)."""
    try:
        line = next(ln for ln in out.splitlines()
                    if ln.startswith("{") and "metric" in ln)
        result = json.loads(line)
        from deepspeed_tpu.monitor.artifacts import record_bench_result

        path = record_bench_result(result)
        print(f"recorded: {path}", file=sys.stderr)
    except Exception as e:  # bench output stays usable without the record
        print(f"artifact recording failed: {e}", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nproc", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--size", default="nano")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--hierarchy", action="store_true",
                    help="add the two-level (data_outer x data_inner) "
                         "lanes; processes map to outer groups")
    ap.add_argument("--overlap", action="store_true",
                    help="add the comm.overlap serial/overlapped lane "
                         "pairs (flat/hier x bf16/int8) measuring the "
                         "exposed-wire fraction")
    ap.add_argument("--overlap-gas", dest="overlap_gas", type=int,
                    default=2, help="micro steps per overlap-lane step "
                                    "(exchange N hides behind micro N+1)")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--proc-id", dest="proc_id", type=int, default=0)
    ap.add_argument("--coord", default="")
    args = ap.parse_args()
    if args.worker:
        worker(args)
        return
    if args.nproc <= 1:
        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            single_process(args)
        out = buf.getvalue()
        sys.stdout.write(out)
        _record(out)
        return
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(args.nproc):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--proc-id", str(pid), "--coord", coord,
             "--nproc", str(args.nproc), "--steps", str(args.steps),
             "--size", args.size, "--seq", str(args.seq),
             "--overlap-gas", str(args.overlap_gas)]
            + (["--hierarchy"] if args.hierarchy else [])
            + (["--overlap"] if args.overlap else []),
            stdout=subprocess.PIPE if pid == 0 else subprocess.DEVNULL,
            stderr=subprocess.STDOUT if pid == 0 else subprocess.DEVNULL,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}))
    out, _ = procs[0].communicate(timeout=3600)
    for p in procs[1:]:
        p.wait(timeout=60)
    out = out.decode()
    sys.stdout.write(out)
    if any(p.returncode for p in procs):
        sys.exit(1)
    _record(out)


if __name__ == "__main__":
    main()
