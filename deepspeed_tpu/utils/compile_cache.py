"""Persistent XLA compile cache placement, in one place.

Every entry point that dispatches to the chip (``chip_smoke.py``,
``bench.py``, ``bin/ds_serve``, ``bin/ds_tune``, the cluster worker)
calls :func:`enable_compile_cache` once before its first compile.  The
library itself never does: a host application owns its own cache
policy, and ``tests/conftest.py`` leaves the cache off.
"""

import os

# <checkout>/.jax_cache: the directory is part of the cache key, so it
# is derived from the package's own location — a path built from a
# temp dir, a pid or the time would never hit twice
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Keep compiled executables across processes.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Returns the directory in effect, or
    None on the CPU backend: CPU runs are the tests (which keep the
    cache off), and XLA:CPU's loader logs a machine-feature warning on
    every hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
