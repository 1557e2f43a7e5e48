"""Where the entry points keep JAX's persistent compilation cache.

Called at the start of each entry point's ``main`` (never at import, so a
library user or a test keeps whatever cache setting it chose).  The cache
key includes the directory, so the directory must not move between runs:
a temp name, a pid or a timestamp would never hit.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/cache.py -> <checkout>
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives in the fixed
    ``<checkout>/.jax_cache`` (git-ignored).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
