"""JAX's persistent compilation cache: one rule for where it lives.

Every entry point (``chip_smoke.py``, the serve and train launchers, the
benchmarks) calls :func:`enable_compile_cache` before its first compile,
so a second run of the same program finds its compiled executables again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no other
  directory is set in code.
* Otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The
  path is fixed — derived from this module's location, never from a temp
  name, a pid or a time — because the directory is part of what a later
  run has to find.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
