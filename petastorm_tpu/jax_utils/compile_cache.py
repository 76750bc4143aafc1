"""JAX's persistent compilation cache for the chip entry points.

``chip_smoke.py`` and ``bench.py`` call :func:`use_compile_cache` before
their first compile; nothing calls it at package import, so the test suite
stays cache-free. The cache key includes the directory, so the path is
fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that itself — this sets nothing), else ``<checkout>/.jax_compile_cache``
(git-ignored). Never a tmp name, a pid or a timestamp.
"""

from __future__ import annotations

import os

CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".jax_compile_cache")


def use_compile_cache():
    """Point JAX's persistent compile cache at its fixed directory and
    return that directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
