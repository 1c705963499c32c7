"""One place that turns on XLA's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` belongs to whoever runs the machine: when it is
set JAX already reads it, and nothing here (or anywhere else in the tree)
sets ``jax_compilation_cache_dir`` over it. When it is unset the cache goes to
one fixed directory inside the checkout, so two processes — a cold run and
the warm run after it — agree on the directory without being told.
"""
from __future__ import annotations

import os

__all__ = ["DEFAULT_CACHE_DIR", "compile_cache_dir", "enable_compile_cache"]

#: <checkout>/.jax_cache (git-ignored): the directory used when the
#: environment names none.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def compile_cache_dir(cache_dir=None) -> str:
    """The directory the cache lives in: ``JAX_COMPILATION_CACHE_DIR`` when
    set, otherwise ``cache_dir`` (a deployment's volume) or
    ``DEFAULT_CACHE_DIR``. Touches no JAX state."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.abspath(str(cache_dir or DEFAULT_CACHE_DIR)))


def enable_compile_cache(cache_dir=None) -> str:
    """Enable the persistent compile cache and return the directory in use
    (see compile_cache_dir). Both entry thresholds drop to "cache
    everything": the defaults skip compiles under a second, which is most of
    a serving step-program set. Call before the first compile that should be
    cached — JAX opens the cache directory once per process."""
    import jax

    path = compile_cache_dir(cache_dir)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
