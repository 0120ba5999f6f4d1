"""JAX's persistent compilation cache, placed one way by every entry point.

``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` before their first compile, so a second
process (or a later run on the same disk) loads compiled programs
instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

# fixed, inside the checkout: the cache key includes the path, so a
# directory that moved between runs would never hit
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache lives at
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
