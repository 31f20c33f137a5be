"""Where this checkout keeps jax's persistent compilation cache.

The cache directory is part of every entry's key, so it has to be the same
path in every process and every run: a path from ``tempfile``, a pid or
the clock never hits. Whoever deploys the daemon places the cache from
outside with ``JAX_COMPILATION_CACHE_DIR``; without it the cache sits in
the checkout, beside the package, in the git-ignored ``.jax_cache``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Returns the cache directory in force. With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax has already read it and no
    directory is set here; otherwise the in-checkout one is. Wherever the
    cache is, every program is kept whatever its compile time: a
    checkpoint load compiles a dozen sub-second view programs besides the
    assembly, and jax's default keeps only what took a second."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
