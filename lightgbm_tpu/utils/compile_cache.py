"""Placement of JAX's persistent compilation cache.

Every process entry point (``chip_smoke.py``, ``bench.py``, the
training CLI, the serve daemon, the ``benchmarks/`` scripts) calls
:func:`configure_compile_cache` once, before its first compile, so all
of them share one cache and a second process skips the grower's
compile.

- ``JAX_COMPILATION_CACHE_DIR`` set: nothing is done here — JAX reads
  the variable itself, and no code names another directory.
- unset: ``jax_compilation_cache_dir`` becomes ``<checkout>/.jax_cache``,
  derived from this file's location. The directory is part of the
  cache key, so it must not depend on ``~``, the working directory, a
  temp dir, a pid or the clock; it is listed in ``.gitignore``.
"""

from __future__ import annotations

import os

__all__ = ["configure_compile_cache", "ENV_VAR", "DEFAULT_DIR"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Point this process's persistent compilation cache at the shared
    directory; returns the directory in effect."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
