"""JAX persistent compilation cache wiring for the launch paths.

The train step and the scrutiny sweep take seconds to minutes to compile,
once per *process*, so every relaunch and ``--resume`` would pay it again
though the program is identical.  XLA's persistent compilation cache keys
serialized executables on the HLO fingerprint and serves later compiles
from disk.

``enable_persistent_cache()`` keeps the cache where
``$JAX_COMPILATION_CACHE_DIR`` says when it is set, and otherwise in one
fixed directory inside the checkout (``<repo>/.jax_cache``, git-ignored):
the path is part of what a later process looks up, so it never moves.
It also drops the min-compile-time / min-entry-size thresholds so the
small helper jits on the relaunch path are cached too.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV) or REPO_CACHE_DIR


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    Idempotent; call before the first jit compilation of the process."""
    import jax

    d = cache_dir()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d
