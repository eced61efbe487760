"""`ds_report` — environment and op-compatibility report.

Reference: deepspeed/env_report.py:23-109 (op install/compat table, torch
and CUDA versions). TPU version: jax/jaxlib/libtpu versions, device
inventory, native-extension (C++) build status from the op_builder
registry.
"""

from __future__ import annotations

import importlib
import sys

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
SUCCESS = f"{GREEN}[OKAY]{END}"
WARNING = f"{YELLOW}[WARNING]{END}"
FAIL = f"{RED}[FAIL]{END}"
NO = f"{YELLOW}[NO]{END}"


def op_report(out=sys.stdout):
    from .ops.op_builder import ALL_OPS

    max_dots = 23
    print("-" * 74, file=out)
    print("op name" + "." * (max_dots - len("op name")) +
          " compatible | built", file=out)
    print("-" * 74, file=out)
    for name, builder_cls in sorted(ALL_OPS.items()):
        builder = builder_cls()
        try:
            compatible = builder.is_compatible()
        except Exception:
            compatible = False
        # probe the cached artifact only — a status report must not
        # compile extensions as a side effect
        try:
            built = builder.lib_path().exists()
        except Exception:
            built = False
        status = SUCCESS if compatible else NO
        built_s = SUCCESS if built else (WARNING if compatible else NO)
        print(f"{name}{'.' * (max_dots - len(name))} {status:>18} | "
              f"{built_s}", file=out)
    print("-" * 74, file=out)


def kernel_report(out=sys.stdout):
    """The Pallas kernel registry's probe table (deepspeed_tpu/kernels):
    each registered hot-loop op, whether its Pallas path would engage
    on this fabric, and the registry's reason when it declines — the
    op_builder table's runtime-kernel sibling."""
    from .kernels import probe_report

    max_dots = 23
    print("-" * 74, file=out)
    print("kernel op" + "." * (max_dots - len("kernel op")) +
          " impl | reason", file=out)
    print("-" * 74, file=out)
    for name, verdict, reason in probe_report():
        status = SUCCESS if verdict == "pallas" else NO
        tail = verdict if verdict == "pallas" else f"{verdict}: {reason}"
        print(f"{name}{'.' * (max_dots - len(name))} {status:>18} | "
              f"{tail}", file=out)
    print("-" * 74, file=out)


def serving_report(out=sys.stdout, engine=None):
    """The serving-side status block: whether the paged-attention
    Pallas kernel would engage on this fabric (and the registry's
    reason when it declines), the configured KV storage dtype, the
    prefix-cache switch, and the resident pinned-session count.
    Without a live engine the config rows report `ServeConfig()`
    defaults — what an engine built here WOULD run with."""
    from .kernels import probe_report

    verdict, reason = "unknown", "not registered"
    for name, v, r in probe_report():
        if name == "paged_attention":
            verdict, reason = v, r
            break
    if engine is not None:
        cfg = engine.config
        kv_dtype = engine.kv.quant_wire or (
            str(cfg.kv_dtype) if cfg.kv_dtype is not None else "dense")
        sessions = f"{engine.resident_sessions}"
    else:
        from .serving.engine import ServeConfig

        cfg = ServeConfig()
        kv_dtype = (str(cfg.kv_dtype) if cfg.kv_dtype is not None
                    else "dense") + " (default)"
        sessions = "0 (no live engine)"
    kern_s = SUCCESS if verdict == "pallas" else NO
    kern_tail = verdict if verdict == "pallas" else f"{verdict}: {reason}"
    pfx = "enabled" if cfg.prefix_cache else "disabled"
    rows = [("paged attention kernel", f"{kern_s} {kern_tail}"),
            ("kv cache dtype", kv_dtype),
            ("prefix cache", pfx),
            ("resident sessions", sessions)]
    print("DeepSpeed-TPU serving status:", file=out)
    for name, val in rows:
        print(f"{name} {'.' * max(1, 24 - len(name))} {val}", file=out)
    print("-" * 74, file=out)


def _devices():
    """(backend, "<n> x <device_kind>") from this process: a chip
    belongs to one process at a time, so the report asks no child."""
    import jax

    d = jax.devices()
    return jax.default_backend(), f"{len(d)} x {d[0].device_kind}"


def debug_report(out=sys.stdout):
    import jax

    rows = [("deepspeed_tpu version",
             importlib.import_module("deepspeed_tpu").__version__),
            ("python version", sys.version.split()[0]),
            ("jax version", jax.__version__)]
    try:
        import jaxlib
        rows.append(("jaxlib version", jaxlib.__version__))
    except Exception:
        pass
    for mod in ("flax", "optax", "numpy"):
        try:
            rows.append((f"{mod} version",
                         importlib.import_module(mod).__version__))
        except Exception:
            rows.append((f"{mod} version", "not installed"))
    backend, devices = _devices()
    rows.append(("backend", backend))
    rows.append(("devices", devices))
    print("DeepSpeed-TPU general environment info:", file=out)
    for name, val in rows:
        print(f"{name} {'.' * max(1, 24 - len(name))} {val}", file=out)


def main(out=sys.stdout):
    op_report(out=out)
    kernel_report(out=out)
    serving_report(out=out)
    debug_report(out=out)


cli_main = main

if __name__ == "__main__":
    main()
