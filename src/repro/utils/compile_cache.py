"""Where the chip entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os

import jax

# A fixed path: the directory is part of the cache key, so a name that
# moved between runs (a temporary name, a pid, a timestamp) would never hit.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def configure() -> str:
    """Place the persistent compilation cache before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to ``<repo>/.jax_cache``.  Called
    by the entry points that run on the chip, never on ``import repro``:
    test compiles for a described chip must not write to the cache.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
