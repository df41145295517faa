"""Where JAX keeps its persistent compilation cache.

Every entry point (``bench.py``, ``chip_smoke.py``, ``dmc_sim``,
``net.serve``, the supervisor's spawn child) calls
:func:`enable_compile_cache` once at start-up; nothing calls it at
import.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself
and wins: this module then sets nothing.  Otherwise the cache lives at
the fixed path ``<repo>/.jax_cache/`` (git-ignored).  The path is part
of what a later run must find again, so it is never built from a tmp
name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
