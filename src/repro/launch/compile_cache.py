"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles real work calls :func:`enable_compile_cache`
once, before its first compile: ``chip_smoke.py``, ``python -m repro.api``,
``python -m repro.launch.train``, ``python -m repro.serve`` and
``benchmarks/run.py``.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* Otherwise, inside a checkout: ``<checkout>/.jax_cache``.  The path is part
  of what a later process must find again, so it is fixed, never built from
  a temporary name, a pid or the time.
* Otherwise (an installed package, no checkout around it): no cache.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
CHECKOUT_CACHE_DIR = _CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory (``None`` when there is none)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not (_CHECKOUT / "pyproject.toml").is_file():
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
