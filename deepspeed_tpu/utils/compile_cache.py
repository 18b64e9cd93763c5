"""Persistent XLA compile cache, placed from outside.

Every entry point that compiles on the chip (``chip_smoke.py`` children,
``benchmark/run.py``) calls :func:`enable` once, before its first compile.

Where the cache lives is the operator's decision, not the program's:

* ``JAX_COMPILATION_CACHE_DIR`` set   — JAX reads the variable itself; this
  module never touches the directory setting.
* ``JAX_COMPILATION_CACHE_DIR`` unset — ``<checkout>/.jax_cache``, derived
  from this file's own location.  The path is part of the cache key, so it
  is fixed: no ``tempfile``, pid or clock goes into it.

The thresholds are lowered in both cases so that every step program and
Mosaic kernel is cached, not only the ones XLA's defaults judge slow.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use (the
    variable wins when set)."""
    import jax
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return os.environ.get(ENV_VAR) or DEFAULT_DIR
