"""JAX's persistent compilation cache, switched on by entry points.

Launchers (``launch/train.py``, ``launch/serve.py``) and ``chip_smoke.py``
call ``enable_compile_cache()`` once at start-up.  Library modules never
call it on import and tests never call it, so a test run leaves the
process-wide JAX config alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and inside the checkout: a directory that moved from run to run
# (temporary, per-pid, per-time) would never be hit again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    this sets no other directory.  Otherwise the cache is ``CACHE_DIR``
    (``<checkout>/.jax_cache``, git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
