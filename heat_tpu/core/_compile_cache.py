"""Where a process keeps JAX's persistent compilation cache.

The library sets nothing at import.  The entry points that start a process
(``chip_smoke.py``, ``perf/run.py``, ``benchmarks/_common.py``, the serving
replica's ``_replica_main``) call :func:`place_compile_cache` once, before
their first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; no directory is
  set in code, so whoever runs the program decides where the cache lives.
- not set: ``<checkout>/.jax_cache`` — one fixed, git-ignored path (the
  path is part of the cache's key, so a temporary name, a pid or a time
  would never hit).  Children inherit it through the same call.

Either way every compiled program is written, however small or quick: the
op engine's programs each compile in well under JAX's default one-second
floor, and a cold start replays hundreds of them.  A floor the operator set
(``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``) is left as it is.

An installed package is not a checkout: where no ``pyproject.toml`` sits
beside the package and nothing is set from outside, nothing is set here
either, and JAX's own defaults stand.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["place_compile_cache", "DEFAULT_CACHE_DIR"]

#: the checkout root is the parent of the ``heat_tpu`` package directory
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: ``<checkout>/.jax_cache``, or None where the package does not run from one
DEFAULT_CACHE_DIR = (
    os.path.join(_CHECKOUT, ".jax_cache")
    if os.path.isfile(os.path.join(_CHECKOUT, "pyproject.toml"))
    else None
)


def place_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory
    (None where there is neither a directory from outside nor a checkout).
    Touches no backend."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = from_env or DEFAULT_CACHE_DIR
    if cache_dir is None:
        return None
    import jax

    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
