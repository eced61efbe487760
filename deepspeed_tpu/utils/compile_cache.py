"""Where this checkout keeps JAX's persistent compilation cache.

The path is part of the cache's key, so it never carries a temporary
name, a pid or a timestamp: a second run in the same checkout (or with
the same `JAX_COMPILATION_CACHE_DIR`) finds what the first compiled.
`chip_smoke.py` calls this before its first JAX use; the
tests leave the cache off (tests/conftest.py says why).
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """-> the cache directory in use.  `JAX_COMPILATION_CACHE_DIR`, when
    set, is JAX's own to read and nothing is set in code; otherwise
    `<checkout>/.jax_cache` (git-ignored)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
