"""Persistent XLA compilation cache for the entry points.

Called from the entry points' ``main`` (``chip_smoke.py``,
``launch/serve.py``, ``benchmarks/serving.py``), never at import, so a
library user's own cache settings are left alone.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path (gitignored), so a later run of the
# same checkout finds the entries again
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself
    and no other path is set here); otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Every compile of at least 0.1 s is kept:
    identical chain programs (two chains of one shape, weights passed as
    arguments) then compile once per process as well as across runs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path
