"""Kernel bench: the Pallas hot-loop registry vs its jnp oracles.

One lane per registered kernel op (deepspeed_tpu/kernels/registry.py),
each running BOTH sides of the registry's contract on identical inputs:

  flash_attention     dense causal flash blocks vs the fp32-softmax
                      einsum chain (tolerance-bounded)
  sparse_attention    flash_sparse blocks under a SparsityConfig layout
                      vs the XLA gather path (tolerance-bounded)
  paged_attention     fused block-table gather + online-softmax decode
                      attention over a paged KV pool — dense, int8 and
                      int4 storage (the quantized dequant fused into
                      the gather) vs `_paged_attend`'s jnp expression
  quant_codec         blockwise int8/int4 quantize + dequantize vs
                      runtime/comm/quant.py (BIT-exact, both wires)
  moe_dispatch        sort-based dispatch (BIT-exact permutation) and
                      gated combine (~1-ulp FMA tolerance) vs
                      moe/dispatch.py

Off-TPU the Pallas side runs under the interpreter (the registry's
`kernels.interpret` escape) — so the CPU lanes are PARITY lanes, not
speed lanes; kernel-vs-jnp timing only means something on a real TPU
backend, where the same script runs the same lanes natively.

`run_dry(...)` is the tier-1 CPU smoke (grad_wire_bench.run_dry
pattern): every lane's parity assert + the `kernel.dispatches` /
`kernel.fallbacks` counter pinning (auto on CPU falls back N-for-N;
forced-pallas-under-interpret dispatches N-for-N), recorded through
monitor/artifacts.py into bench_artifacts/runs/ (the PR-2 durable-
artifact rule).

Usage: python tools/kernel_bench.py [--steps 20] [--dry-run]
           [--ops flash_attention,quant_codec]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))


def _tree_np(x):
    import numpy as np

    if isinstance(x, (tuple, list)):
        return [np.asarray(v) for v in x]
    return [np.asarray(x)]


def _parity(a, b, exact: bool, tol: float):
    """-> (ok, max_abs_diff | None).  Exact lanes compare bitwise
    (NaN == NaN: the codec's non-finite marker reconstructs as NaN);
    tolerance lanes compare max-abs over fp32."""
    import numpy as np

    aa, bb = _tree_np(a), _tree_np(b)
    if len(aa) != len(bb):
        return False, None
    if exact:
        ok = all(x.dtype == y.dtype
                 and np.array_equal(x, y, equal_nan=True)
                 for x, y in zip(aa, bb))
        return ok, 0.0 if ok else None
    diff = max(float(np.max(np.abs(x.astype(np.float64)
                                   - y.astype(np.float64))))
               if x.size else 0.0
               for x, y in zip(aa, bb))
    return diff <= tol, diff


def make_lanes(small: bool = True):
    """[{name, op, variant, args, kwargs, info, exact, tol}] — one
    entry per (op, variant/mode) parity lane.  `small` keeps shapes
    interpreter-friendly for the tier-1 dry-run; the CLI bench scales
    the attention lanes up."""
    import numpy as np

    import jax.numpy as jnp
    from deepspeed_tpu.moe.dispatch import topk_routing
    from deepspeed_tpu.ops.sparse_attention import DenseSparsityConfig
    from deepspeed_tpu.runtime.comm.quant import (quantize_blockwise_ref,
                                                  quantize_rows)
    from deepspeed_tpu.serving.kv_cache import pool_rows

    rng = np.random.RandomState(0)
    lanes = []

    def f32(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    # -- flash attention (op 4): BSHD, seq divisible by the blocks ----
    B, S, H, D = (1, 128, 2, 128) if small else (2, 512, 4, 128)
    q, k, v = f32(B, S, H, D), f32(B, S, H, D), f32(B, S, H, D)
    lanes.append(dict(
        name="flash_attention", op="flash_attention", variant="default",
        args=(q, k, v), kwargs={"causal": True},
        info={"seq_len": S, "kv_len": S}, exact=False, tol=2e-5))

    # -- sparse attention (satellite 1): dense layout + causal mask ---
    sb = 64
    layout = DenseSparsityConfig(num_heads=H, block=sb).make_layout(S)
    lanes.append(dict(
        name="sparse_attention", op="sparse_attention", variant="default",
        args=(q[..., :64], k[..., :64], v[..., :64], layout, sb),
        kwargs={"causal": True},
        info={"plain": True, "block": sb, "head_dim": 64},
        exact=False, tol=2e-5))

    # -- paged attention (op 1): decode step over a block-table walk --
    R, T, Hh, Dh, bs, W = (2, 1, 2, 128, 4, 4) if small \
        else (4, 1, 4, 128, 16, 8)
    nblocks = R * W + 1
    cache_rows = nblocks * bs
    ck_f = f32(cache_rows, Hh, Dh)
    cv_f = f32(cache_rows, Hh, Dh)
    tables = jnp.asarray(
        rng.randint(0, nblocks, (R, W)), jnp.int32)
    L = W * bs
    q_pos = jnp.asarray(rng.randint(1, L, (R, T)), jnp.int32)
    pq = f32(R, T, Hh, Dh)
    for mode in ("dense", "int8", "int4"):
        if mode == "dense":
            ck, cv = pool_rows(ck_f), pool_rows(cv_f)
        else:  # (payload rows, per-(row, head) scales)
            (ck, sk), (cv, sv) = (quantize_rows(c, mode)
                                  for c in (ck_f, cv_f))
            ck, cv = (pool_rows(ck), sk), (pool_rows(cv), sv)
        lanes.append(dict(
            name=f"paged_attention_{mode}", op="paged_attention",
            variant="default", args=(pq, ck, cv, tables, q_pos),
            kwargs={"kv_mode": mode, "block_size": bs},
            info={"block_size": bs, "table_width": W, "q_len": T,
                  "num_heads": Hh, "head_dim": Dh, "kv_mode": mode,
                  "kv_itemsize": 4},
            exact=False, tol=1e-5))

    # -- quant codec (op 2): both wires, both directions, non-finites -
    n = 4096 if small else 1 << 20
    x = np.asarray(rng.randn(n), np.float32)
    x[7], x[133], x[1025] = np.inf, -np.inf, np.nan  # marker path
    x = jnp.asarray(x)
    block = 128
    for wire in ("int8", "int4"):
        lanes.append(dict(
            name=f"quant_codec_quantize_{wire}", op="quant_codec",
            variant="quantize", args=(x, block, wire), kwargs={},
            info={"block": block}, exact=True, tol=0.0))
        payload, scales = quantize_blockwise_ref(x, block, wire)
        lanes.append(dict(
            name=f"quant_codec_dequantize_{wire}", op="quant_codec",
            variant="dequantize", args=(payload, scales, wire, n),
            kwargs={}, info={"block": block}, exact=True, tol=0.0))

    # -- moe dispatch/combine (op 3): real top-k routing -------------
    N, E, Cc, kk, Dm = (16, 4, 6, 2, 128) if small \
        else (256, 8, 48, 2, 256)
    e = np.exp(rng.randn(N, E))
    probs = jnp.asarray(e / e.sum(axis=1, keepdims=True), jnp.float32)
    eidx, gate, pos, keep, _aux = topk_routing(probs, kk, Cc)
    xtok = f32(N, Dm)
    lanes.append(dict(
        name="moe_dispatch", op="moe_dispatch", variant="dispatch",
        args=(xtok, eidx, pos, keep, E, Cc), kwargs={},
        info={"model_dim": Dm}, exact=True, tol=0.0))
    expert_out = f32(E, Cc, Dm)
    lanes.append(dict(
        name="moe_combine", op="moe_dispatch", variant="combine",
        args=(expert_out, eidx, gate, pos, keep), kwargs={},
        info={"model_dim": Dm}, exact=False, tol=1e-6))
    return lanes


def run_lanes(lanes, steps: int = 0):
    """Each lane through BOTH registry sides; parity always, timing
    when steps > 0.  -> {lane name: entry}."""
    import jax
    import numpy as np

    from deepspeed_tpu.kernels import kernel_config, registry

    results = {}
    for lane in lanes:
        def call(impl):
            return registry.dispatch(
                lane["op"], *lane["args"], variant=lane["variant"],
                impl=impl, info=lane["info"], **lane["kwargs"])

        oracle = call("jnp")
        with kernel_config(interpret=True):
            kern = call("pallas")
        ok, diff = _parity(kern, oracle, lane["exact"], lane["tol"])
        assert ok, (f"{lane['name']}: kernel/oracle parity broken "
                    f"(exact={lane['exact']}, tol={lane['tol']}, "
                    f"max_abs_diff={diff})")
        entry = {"parity": "bitwise" if lane["exact"] else "tolerance",
                 "max_abs_diff": diff}
        if steps > 0:
            # jnp/jax arrays (and (payload, scales) pairs) become jit
            # ARGUMENTS so XLA cannot constant-fold the lane away;
            # python scalars and numpy layouts stay static closures
            def dyn(a):
                return isinstance(a, jax.Array) or (
                    isinstance(a, tuple)
                    and all(isinstance(x, jax.Array) for x in a))

            dyn_idx = [i for i, a in enumerate(lane["args"]) if dyn(a)]
            dyn_args = [lane["args"][i] for i in dyn_idx]

            def timed(impl):
                def f(*xs):
                    args = list(lane["args"])
                    for j, i in enumerate(dyn_idx):
                        args[i] = xs[j]
                    return registry.dispatch(
                        lane["op"], *args, variant=lane["variant"],
                        impl=impl, info=lane["info"], **lane["kwargs"])
                return jax.jit(f)

            for impl, label in (("jnp", "jnp_ms"), ("pallas",
                                                    "pallas_ms")):
                with kernel_config(interpret=True):
                    fn = timed(impl)
                    jax.block_until_ready(fn(*dyn_args))  # compile
                    t = []
                    for _ in range(steps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(*dyn_args))
                        t.append(time.perf_counter() - t0)
                entry[label] = round(float(np.median(t)) * 1e3, 3)
        results[lane["name"]] = entry
    return results


def pin_counters(lanes):
    """The dispatch-counter contract, pinned against real dispatches:
    impl='auto' off-TPU falls back N-for-N (`kernel.fallbacks`);
    forced pallas under the interpret escape dispatches N-for-N
    (`kernel.dispatches`).  On a TPU backend auto selects the kernel
    instead, so the pin only asserts the CPU side there."""
    import jax

    from deepspeed_tpu.kernels import kernel_config, registry
    from deepspeed_tpu.monitor.counters import COUNTERS

    def run_all(impl_cfg):
        with kernel_config(**impl_cfg):
            for lane in lanes:
                registry.dispatch(
                    lane["op"], *lane["args"], variant=lane["variant"],
                    info=lane["info"], **lane["kwargs"])

    on_tpu = jax.default_backend() == "tpu"
    snap = COUNTERS.snapshot()
    run_all({"impl": "auto"})
    d = COUNTERS.delta_since(snap)
    auto = {"dispatches": int(d.get("kernel.dispatches",
                                    {}).get("calls", 0)),
            "fallbacks": int(d.get("kernel.fallbacks",
                                   {}).get("calls", 0))}
    if not on_tpu:
        assert auto == {"dispatches": 0, "fallbacks": len(lanes)}, auto

    snap = COUNTERS.snapshot()
    run_all({"impl": "pallas", "interpret": True})
    d = COUNTERS.delta_since(snap)
    forced = {"dispatches": int(d.get("kernel.dispatches",
                                      {}).get("calls", 0)),
              "fallbacks": int(d.get("kernel.fallbacks",
                                     {}).get("calls", 0))}
    assert forced == {"dispatches": len(lanes), "fallbacks": 0}, forced
    return {"auto": auto, "forced_pallas": forced}


def run_dry(artifact_root=None):
    """Tier-1 CPU dry-run (the grad_wire_bench.run_dry pattern):
    every registered op's kernel-vs-oracle parity assert + the
    kernel.* counter pinning, recorded as a durable artifact.
    Returns the recorded result dict."""
    import jax

    from deepspeed_tpu.monitor.artifacts import record_bench_result

    lanes = make_lanes(small=True)
    results = run_lanes(lanes, steps=0)
    counters = pin_counters(lanes)
    result = {
        "metric": "kernel_registry_dryrun",
        "platform": str(jax.default_backend()),
        "value": len(results),
        "unit": "parity_lanes",
        "counters": counters,
        **results,
    }
    result["artifact"] = record_bench_result(result, root=artifact_root)
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=20,
                    help="timing reps per lane (median reported)")
    ap.add_argument("--ops", default="",
                    help="comma-separated op-name filter (lane names "
                         "match by prefix)")
    ap.add_argument("--dry-run", action="store_true",
                    help="parity + counter pinning only (the tier-1 "
                         "lane); records under bench_artifacts/")
    args = ap.parse_args()
    if args.dry_run:
        result = run_dry()
        print(json.dumps(result, indent=2))
        return

    import jax

    from deepspeed_tpu.monitor.artifacts import record_bench_result

    lanes = make_lanes(small=jax.default_backend() != "tpu")
    if args.ops:
        wanted = tuple(s.strip() for s in args.ops.split(",") if s.strip())
        lanes = [ln for ln in lanes if ln["op"] in wanted
                 or ln["name"].startswith(wanted)]
        if not lanes:
            raise SystemExit(f"--ops {args.ops!r} matched no lanes")
    results = run_lanes(lanes, steps=args.steps)
    counters = pin_counters(lanes)
    result = {
        "metric": "kernel_registry_bench",
        "platform": str(jax.default_backend()),
        "steps": args.steps,
        "value": len(results),
        "unit": "parity_lanes",
        "counters": counters,
        **results,
    }
    print(json.dumps(result, indent=2))
    try:
        path = record_bench_result(result)
        print(f"recorded: {path}", file=sys.stderr)
    except Exception as e:  # bench output stays usable without the record
        print(f"artifact recording failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
