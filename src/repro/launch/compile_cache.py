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
    """Turn on the persistent compilation cache, keyed on the program's
    metadata too; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    this sets no other directory.  Otherwise the cache is ``CACHE_DIR``
    (``<checkout>/.jax_cache``, git-ignored).
    """
    # the key covers the executable's metadata, whose op_name scopes a
    # profile names operations by: without it an executable compiled from
    # code with other scopes (or none) is loaded in place of this code's
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
