"""Test harness: virtual 8-device CPU mesh.

The reference tests run real NCCL on 2-4 local GPUs via the
@distributed_test fork-N-processes fixture
(/root/reference/tests/unit/common.py:16-100). TPU-natively we instead run
single-process with XLA's host-platform device virtualization: 8 fake CPU
devices, so every sharding/collective path executes for real (SPMD) without
hardware. This must run before jax initializes, hence conftest import time.
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
if "--xla_backend_optimization_level" not in os.environ["XLA_FLAGS"]:
    # the suite is compile-dominated on the 1-core box and every test is
    # a CORRECTNESS check (parity between two programs, both compiled the
    # same way) — O0 cuts wall-clock ~40% with identical pass/fail.
    # Perf measurements (benchmarks/, tools/) do NOT go through conftest.
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"
os.environ["JAX_PLATFORMS"] = "cpu"  # the tests never touch the chip

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

# NOTE: a persistent XLA compilation cache was tried here and reverted:
# XLA:CPU AOT reload warns about mismatched machine features on this host
# ("could lead to execution errors such as SIGILL") and produced small
# cross-test numerical drift. Re-evaluate on a host where the AOT loader
# accepts the feature set.

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_mesh():
    """Each test builds meshes explicitly; clear the global between tests."""
    yield
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod._CURRENT_MESH = None
    # engines install the comm.moe wire selection process-globally
    # (moe/dispatch.py) — restore the seed default so a MoE engine test
    # can't leak its dispatch engine into a later direct-layer test
    from deepspeed_tpu.moe import dispatch as moe_dispatch

    moe_dispatch.set_wire_config(moe_dispatch.MoEWireConfig())


@pytest.fixture
def native(monkeypatch):
    """What the package sees on the chip: kernels lower through Mosaic
    and the kernel registry's probe says TPU.  Nothing can run or be
    lowered for the CPU under it: a test traces, resolves, or compiles
    for a described chip (tests/test_tpu_compile.py)."""
    from deepspeed_tpu.ops import pallas_backend

    monkeypatch.setattr(pallas_backend, "interpret", lambda: False)


@pytest.fixture
def chip_rule(monkeypatch):
    """`with chip_rule(op):` — the kernel registry answers for `op` as it
    does on the chip (its shape rule decides, nothing forced) while the
    kernel it picks runs under the Pallas interpreter; every other op
    takes its oracle.  An engine built and run inside it serves through
    the kernel exactly where the chip's would."""
    import contextlib

    from deepspeed_tpu.kernels import kernel_config, registry

    @contextlib.contextmanager
    def scope(op: str):
        with monkeypatch.context() as chip, \
                kernel_config(impl="jnp", ops={op: "auto"}):
            chip.setattr(registry, "_on_tpu", lambda: True)
            yield

    return scope
