"""Where the entry points keep JAX's persistent compilation cache.

Called by each entry point (``chip_smoke.py``, ``examples/``,
``benchmarks/run.py``, the RPC endpoint) before its first compile — never as
a side effect of importing the library:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set in code.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path
  is part of the cache key, so it is fixed — never built from a temporary
  name, a pid or the time — and a second run of the same program on the
  same machine finds its compiled steps again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
