"""The one question every Pallas kernel module of this package asks of
the backend: run natively, or under the Pallas interpreter?

Kernel modules call `pallas_backend.interpret()` through the module, so
tests/test_tpu_compile.py — which compiles for a described chip while
`jax.default_backend()` still says "cpu" — patches this one name.
"""

import jax


def interpret() -> bool:
    return jax.default_backend() != "tpu"
