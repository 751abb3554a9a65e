"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks.run``, ``repro.launch.serve``)
call ``enable_compile_cache()`` first thing; nothing calls it at import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory itself
and this sets nothing.  Otherwise the cache lives at one fixed path inside
the checkout, ``.jax_cache/`` (gitignored): a cache's directory is part of
its key, so a per-run path would never hit.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
