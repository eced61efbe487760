"""1-bit/int8 Adam wire measurement across a REAL serialization boundary.

The single-process CPU-mesh bench (tools/onebit_bench.py) cannot see
wire effects — all "collectives" are memory movement inside one address
space. Here N jax.distributed processes on localhost talk over TCP, so
cross-process collective payloads pay a real byte-proportional
serialize/send/deserialize cost: the first fabric where "fewer bytes"
can actually buy "less time".

Two measurements per wire variant {dense fp32, bucketed fp32, bucketed
blockwise-int8 (dense Adam semantics, comm/quant.py), sign, onebit
int8}:
  1. engine step time (median) — end-to-end through the fused hot path;
  2. a bare cross-process mean of an n_params-sized payload at the
     variant's wire dtype — isolates the transport from optimizer FLOPs.

Reference twin: tests/onebit/test_nccl_perf.py (NCCL compressed_allreduce
vs torch.distributed.all_reduce over sockets).

Usage: python tools/onebit_bench_mp.py [--nproc 2] [--steps 20]
           [--size nano] [--seq 32]
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


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker(args):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=args.coord,
                               num_processes=args.nproc,
                               process_id=args.proc_id)
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT, gpt2_config

    dp = jax.device_count()
    cfg_base = {
        "train_batch_size": dp,
        "zero_optimization": {"stage": 0},
        "mesh": {"data": dp},
        "steps_per_print": 0,
    }
    model_cfg = gpt2_config(args.size, vocab_size=512,
                            max_seq_len=args.seq, dropout=0.0,
                            embed_dropout=0.0)
    n_params = GPT(model_cfg).num_params()
    rng = np.random.RandomState(0)  # identical stream on every process
    tok = rng.randint(0, 512, (dp, args.seq + 1)).astype(np.int32)
    batch = (tok[:, :-1], tok[:, 1:])

    def run(opt, wire):
        params = {"lr": 1e-4, "weight_decay": 0.0}
        if opt == "OneBitAdam":
            params["freeze_step"] = 8
            params["wire"] = wire
        cfg = dict(cfg_base)
        if wire == "bucketed":
            # dense Adam through the fused grad-wire buckets
            # (runtime/comm/bucketing.py) instead of per-leaf psums
            cfg["comm"] = {"gradient_reduction": "bucketed"}
        elif wire == "bucketed_int8":
            # dense Adam semantics over the blockwise-quantized gather
            # wire (comm/quant.py): ~1 byte/elem + fp16 scales, fp32
            # accumulation — the dense-algorithm counterpart to the
            # 1-bit optimizer's error-feedback int8 momentum wire
            cfg["comm"] = {"gradient_reduction": "bucketed",
                           "wire_dtype": "int8"}
        cfg["optimizer"] = {"type": opt, "params": params}
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT(model_cfg), dist_init_required=False,
            config_params=cfg)
        if opt == "OneBitAdam":
            assert getattr(engine, "_onebit_hot", False)
        if wire.startswith("bucketed"):
            assert engine.bucket_plan is not None
        for _ in range(12):  # compile + freeze_step crossing
            engine.forward(batch); engine.backward(); engine.step()
        t = []
        for _ in range(args.steps):
            t0 = time.perf_counter()
            loss = engine.forward(batch)
            engine.backward(); engine.step()
            loss.block_until_ready()
            t.append(time.perf_counter() - t0)
        return float(np.median(t)), float(loss)

    results = {}
    for opt, wire in [("Adam", "dense"), ("Adam", "bucketed"),
                      ("Adam", "bucketed_int8"),
                      ("OneBitAdam", "sign"), ("OneBitAdam", "int8")]:
        sec, loss = run(opt, wire)
        results[wire] = {"step_ms": round(sec * 1e3, 2),
                         "loss": round(loss, 4)}

    # bare transport: cross-process mean of an n_params payload at each
    # wire dtype (the isolated bytes-vs-time curve)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import jax.numpy as jnp

    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    per = len(devs) // args.nproc
    mesh = Mesh(np.array(devs).reshape(args.nproc, per), ("proc", "dev"))
    row = NamedSharding(mesh, P("proc"))
    out = NamedSharding(mesh, P())
    # all-gather semantics (identity resharding P("proc") -> replicated):
    # the wire carries the RAW dtype, exactly like the int8 optimizer's
    # all_to_all+all_gather phases.  (An arithmetic reduce would upcast
    # before the transfer and measure fp32 bytes regardless.)
    for elems in [n_params, 1 << 22, 1 << 24]:  # find the byte-bound knee
        for name, dt in [("fp32", np.float32), ("int8", np.int8)]:
            local = np.ones((1, elems), dt)
            garr = jax.make_array_from_process_local_data(
                row, local, (args.nproc, elems))
            red = jax.jit(lambda x: x, out_shardings=out)
            red(garr).block_until_ready()  # compile
            t = []
            for _ in range(max(10, args.steps)):
                t0 = time.perf_counter()
                red(garr).block_until_ready()
                t.append(time.perf_counter() - t0)
            results[f"gather_{name}_{elems}"] = {
                "ms": round(float(np.median(t)) * 1e3, 3),
                "payload_bytes": int(elems * np.dtype(dt).itemsize)}

    if args.proc_id == 0:
        print(json.dumps({
            "metric": "onebit_wire_2proc_tcp",
            "platform": "cpu",
            "n_params": int(n_params),
            "world": {"processes": args.nproc, "devices": dp},
            **results,
        }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nproc", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--size", default="nano")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--proc-id", dest="proc_id", type=int, default=0)
    ap.add_argument("--coord", default="")
    args = ap.parse_args()
    if args.worker:
        worker(args)
        return
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(args.nproc):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--proc-id", str(pid), "--coord", coord,
             "--nproc", str(args.nproc), "--steps", str(args.steps),
             "--size", args.size, "--seq", str(args.seq)],
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
    # durable artifact under bench_artifacts/runs/ + manifest; the
    # printed JSON stays the primary output
    try:
        line = next(ln for ln in out.splitlines()
                    if ln.startswith("{") and "metric" in ln)
        from deepspeed_tpu.monitor.artifacts import record_bench_result

        path = record_bench_result(json.loads(line))
        print(f"recorded: {path}", file=sys.stderr)
    except Exception as e:
        print(f"artifact recording failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
