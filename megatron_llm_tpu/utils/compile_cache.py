"""JAX's persistent compilation cache, placed once for every entry point.

A chip run pays minutes of XLA/Mosaic compilation per cold process; the
persistent cache turns the second run of the same program into a disk
read. The directory is part of the cache key, so it must not move:
where `JAX_COMPILATION_CACHE_DIR` is set JAX already honours it and no
directory is set in code; otherwise the cache lives at the fixed,
git-ignored `<checkout>/.jax_cache`.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Call first thing in an entry point, before anything compiles.
    Returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
