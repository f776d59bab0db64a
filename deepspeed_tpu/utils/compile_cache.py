"""JAX's persistent compilation cache, placed from outside or at one fixed
path — the only code in the repository that names a cache directory.

A compiled program is only found again under the same directory (the path
is part of how a run finds its entries), so the directory never contains a
temporary name, a pid or a time. Whoever runs the program places the cache
with ``JAX_COMPILATION_CACHE_DIR``; JAX reads that variable itself, and
then nothing here sets a directory. Without it the cache lives in
``<checkout>/.jax_cache`` (ignored by git).
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process and return the
    directory in use. Call before the first compilation. Every program is
    kept, however small or quick to compile: the serving path is dozens of
    sub-second programs, which the default thresholds would skip."""
    import jax

    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
