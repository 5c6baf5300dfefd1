"""JAX's persistent compilation cache, placed from outside the program.

Called from the `main()` of each entry point (`launch/serve.py`,
`launch/train.py`, `chip_smoke.py`), never at import time. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here. Otherwise the cache goes to a fixed ``<repo root>/.jax_cache``
(git-ignored): the directory is part of the cache key, so a path that moved
between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
