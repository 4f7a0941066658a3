"""Where compiled programs persist between runs: the one compile-cache rule.

JAX's persistent compilation cache is always on. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no directory
is set in code; otherwise the cache lives at ``<checkout>/.jax_cache`` —
an absolute path derived from the package, never the working directory,
because the path is part of what makes the next run find the entries.
Every entry point (server.py, each bench.py main, tools/train.py,
tools/profile_serve.py) calls :func:`enable_compilation_cache` once before
its first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent.parent
DEFAULT_JAX_CACHE_DIR = str(CHECKOUT / ".jax_cache")
DEFAULT_AOT_CACHE_DIR = str(CHECKOUT / ".aot_cache")


def enable_compilation_cache() -> None:
    """Turn JAX's persistent compilation cache on, by the rule above."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
