"""Persistent XLA compilation cache, placed from outside.

A fresh process pays the full XLA compile of every train / tick / prefill
program on its first dispatch — tens of seconds on the chip.  The
persistent cache turns a warm start into a deserialize.  One rule, for
every entry point:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment — JAX reads it
  itself; this module sets NO directory in code, so whoever launches the
  program (a runner that keeps a cache between calls, a CI job) decides
  where it lives.
* unset — the fixed ``<checkout>/.jax_cache`` (git-ignored).  Never a
  temp name, pid or timestamp: the path is part of the cache key, so a
  directory that moves never hits.

Only ENTRY POINTS call :func:`enable_compile_cache` (the examples'
``setup_platform``, ``chip_smoke.py``, ``benchmarks/run.py``).  Library
code never does: a constructor must not reconfigure process-global JAX
state, and tests that count compiles must not depend on a cache on disk.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; returns the directory in
    effect.  Call before the first compile (the cache latches on/off
    there)."""
    import jax
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # persist every program, however fast the compile or small the entry
    # (1, not 0: the cache reads 0 as "unset" and substitutes its default)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 1)
    return jax.config.jax_compilation_cache_dir
