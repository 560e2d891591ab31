"""Where XLA's persistent compilation cache lives.

Called by the entry points (``chip_smoke.py``, ``bench.py``, ``run.main``),
never at import: library users and the CPU test-suite keep jax's default
(no persistent cache).

The cache directory is part of every cache key, so it must not move between
runs: either the operator places it with ``JAX_COMPILATION_CACHE_DIR`` (jax
reads that itself at import, and this module then sets nothing), or it is
one fixed directory inside the checkout.
"""

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
