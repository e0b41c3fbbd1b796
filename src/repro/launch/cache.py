"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module leaves it alone. Otherwise the cache lives at ``<checkout>/.jax_cache``
(git ignores it): a fixed path, because the path is part of the cache key,
so a directory named after a temporary file, a pid or the time never hits.
"""
from __future__ import annotations

import os

#: The repository checkout this package was imported from.
CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
