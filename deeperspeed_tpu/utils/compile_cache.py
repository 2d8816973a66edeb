"""Where JAX's persistent compilation cache lives -- decided in one place.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no code
here (or anywhere in the repo) names another directory.  Otherwise the cache
is ``<checkout>/.jax_cache``: a fixed, git-ignored path, because the path is
part of how a cache is found again -- a temporary name, a pid or a time in it
means the next process never hits.
"""

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache(min_compile_secs=1.0):
    """Turn the persistent cache on; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
