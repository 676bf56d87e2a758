"""JAX's persistent compilation cache, turned on by the entry points.

Library code never calls this: importing a module must not change how
the process compiles. ``chip_smoke.py``, ``launch.serve``,
``launch.train`` and ``benchmarks.run`` call :func:`enable_compile_cache`
first thing, so a second run of a full-width program reads its
executables back instead of compiling them again.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache``. The path is part of each entry's key, so it is
#: fixed: never a temporary name, a pid or a time.
CHECKOUT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is set here; otherwise the cache is :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
