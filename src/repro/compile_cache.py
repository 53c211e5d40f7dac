"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
:func:`enable_compile_cache` once before they compile anything; importing
``repro`` never does.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  else is set here.
* Otherwise the cache lives in ``<checkout>/.jax_cache``.  The path is
  fixed — never built from a temporary name, a process id or the time —
  because the directory is what lets a later run find the programs.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
