"""Persistent XLA compilation cache, placed from outside or at one fixed path.

The cache's directory is part of its key, so a directory that moves never
hits. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
module sets no other; where it is not, the cache lives at ``<repo>/.jax_cache``
(git-ignored), the same path for every process of a checkout. The tests
(``tests/conftest.py``) and ``chip_smoke.py`` go through
``enable_compilation_cache``.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on; returns the directory in
    use. Call before the first compilation."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
