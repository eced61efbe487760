"""deepspeed_tpu.kernels — the Pallas hot-loop op registry.

One kernel-selection mechanism for the whole repo (registry.py):
op_builder-style probed Pallas implementations with their original jnp
expressions kept as pinned correctness oracles.  See
docs/tutorials/kernels.md.
"""

from .registry import (KERNEL_IMPLS, KERNEL_OPS, KernelConfig, dispatch,
                       get_kernel, get_kernel_config, kernel_config,
                       probe_report, resolve_impl)

__all__ = [
    "KERNEL_IMPLS", "KERNEL_OPS", "KernelConfig", "dispatch", "get_kernel",
    "get_kernel_config", "kernel_config", "probe_report", "resolve_impl",
]
