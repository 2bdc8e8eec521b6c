"""JAX's persistent compile cache for the entry points.

chip_smoke.py and the agent call `enable_compile_cache()`
before their first compile.  Nothing calls it at import time, so the
test suite compiles without a cache.
"""

from __future__ import annotations

import os

# a fixed path: the cache key includes it, so a directory that moves
# (a temp name, a pid) never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Returns the cache directory in use.  When
    JAX_COMPILATION_CACHE_DIR is set JAX already reads it and nothing
    is set here; otherwise the cache goes to `<repo>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
