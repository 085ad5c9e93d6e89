"""Persistent XLA compile cache placement, in one place.

Every entry point that dispatches to the chip (``chip_smoke.py``,
``bench.py``, ``bin/ds_serve``, ``bin/ds_tune``, the cluster worker)
calls :func:`enable_compile_cache` once before its first compile.  The
library itself never does: a host application owns its own cache
policy, and ``tests/conftest.py`` leaves the cache off.

A cached executable keeps the NAMES of the tree that compiled it.  JAX
leaves an instruction's metadata (``op_name``: the flax modules and
``jax.named_scope``s it was traced under; source file and line) out of
the cache key unless ``jax_compilation_cache_include_metadata_in_key``
is set, and this file sets nothing of the kind: a program whose
computation is unchanged hits the entry an EARLIER tree wrote, and what
is loaded carries that tree's paths — its ``as_text()`` and with it
the ``tf_op`` stat a device profile gives its events (checked on a
v5e, PR 60: the same function under ``named_scope("beta")`` after a
run under ``named_scope("alpha")`` had filled the cache hits, and its
text reads ``jit(f)/alpha/dot_general``; PERF.md section 7).  A program
that holds a Pallas kernel misses instead whenever a line moves in a
file on the kernel's Python stack: the serialized Mosaic body carries
source locations, and those are part of the key.  Timings, tokens and
losses are the same either way; what goes stale is every reading that
resolves a device event to its path: ``engine.module_profile()``,
``ds_serve --profile-steps``, the benchmark's ``scope.*`` metrics.
After a change to a scope or a module's name, take such readings from
a cache this tree filled (``JAX_COMPILATION_CACHE_DIR`` pointed at a
new directory for that run), not from the checkout's ``.jax_cache``.
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
