"""Where JAX's persistent compilation cache lives.

One helper, used by the benchmark harness (`benchmarks.common`) and by
`chip_smoke.py`. The cache directory is part of every entry's key, so
it never comes from a temporary name, a process id or the clock:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this
    module sets no other directory;
  * unset: the cache goes to ``results/.jax_cache`` inside the checkout
    (git-ignored).
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory (src/repro/ -> <root>/results).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "results", ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on for every program this
    process compiles; returns the directory in use."""
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
