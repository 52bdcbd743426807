"""Fixed places inside the checkout for what the program keeps between runs.

Two caches outlive a process: JAX's persistent compilation cache and the
measured autotune winners (:mod:`repro.kernels.autotune`).  Both live at
fixed paths inside the checkout, never under the home directory: two
checkouts on one machine (a change and its parent, compared on the same
chip) must not read each other's entries.  The path is also part of the
compilation cache's key, so it must not depend on a temp name, a pid or
the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["AUTOTUNE_CACHE_DIR", "COMPILE_CACHE_DIR", "enable_compile_cache"]

_CHECKOUT = Path(__file__).resolve().parents[2]  # <checkout>/src/repro/caches.py
COMPILE_CACHE_DIR = _CHECKOUT / ".jax_cache"
AUTOTUNE_CACHE_DIR = _CHECKOUT / ".autotune_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Entry points call it from ``main()``, never at
    import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
