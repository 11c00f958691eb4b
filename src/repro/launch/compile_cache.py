"""JAX's persistent compilation cache, at one fixed place.

A compiled program is found again only where the cache directory is the
same from run to run, so the directory is never made from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

#: ``<repo>/.jax_cache`` (git-ignored), next to ``src/``.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for this process.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing (returns None). Otherwise the cache goes to
    ``CACHE_DIR``, whose normalized path is returned. Call it from entry
    points only — tests leave the cache off.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = os.path.normpath(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
