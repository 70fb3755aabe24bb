"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, ``repro.launch.serve``)
and for the test suite:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing here
    sets any other directory.
  * otherwise — the fixed directory ``<checkout>/.jax_cache`` (git-ignored).
    The path is part of the cache key, so it never depends on a temp dir,
    a pid or the time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Apply the rule above and return the cache directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
