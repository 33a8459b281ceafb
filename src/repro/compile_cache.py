"""JAX's persistent compilation cache, as the entry points set it up.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
overrides it.  Otherwise the cache lives at the fixed ``<repo>/.jax_cache``:
the directory is part of what a later run must find again, so it never
depends on a temporary directory, a PID or the time.  Only entry points
call ``enable`` (under their ``__main__`` check); importing this module or
the program sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable(repo_root: Path) -> Path:
    """Turn the persistent cache on and return its directory.  Every
    compile is written, however quick: a cold chip run compiles dozens of
    small programs, and a warm one should find all of them."""
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = str(Path(repo_root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return Path(cache_dir)
