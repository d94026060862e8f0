"""JAX's persistent compilation cache, shared by every entry point.

``launch/serve.py``, ``launch/train.py`` and ``chip_smoke.py`` call
:func:`enable` before their first compile, so one run reuses what an
earlier one compiled. The cache directory is part of the cache key, so
it is one fixed path: ``JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads it itself), otherwise ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
