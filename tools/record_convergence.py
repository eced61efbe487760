"""Record the pinned convergence baseline (tests/convergence/*.json).

Mirrors the reference's pinned-curve methodology
(/root/reference/tests/model/Megatron_GPT2/run_func_test.py:20-36: fixed
config, fixed seed, assert the metric within tolerance). Run on the 8-device
CPU mesh — the same environment the regression test uses:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/record_convergence.py
"""

from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tests"))

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # a CPU-mesh recording
jax.config.update("jax_threefry_partitionable", True)

from convergence_common import run_curve, BASELINE_PATH, CONFIG  # noqa: E402


def main():
    losses = run_curve()
    os.makedirs(os.path.dirname(BASELINE_PATH), exist_ok=True)
    with open(BASELINE_PATH, "w") as f:
        json.dump({"config": CONFIG, "losses": losses}, f, indent=1)
    print(f"wrote {BASELINE_PATH}: first={losses[0]:.4f} "
          f"last={losses[-1]:.4f}")


if __name__ == "__main__":
    main()
