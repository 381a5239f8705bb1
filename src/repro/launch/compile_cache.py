"""JAX's persistent compilation cache for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is
part of what a later run must find again, so it never depends on a temp
name, a pid or the time. Only entry points call this; library modules and
tests leave the cache alone.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_repo_compile_cache() -> str:
    """Point JAX's compilation cache at the checkout unless the environment
    already placed it. Returns the directory in use."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
