"""Where jax's persistent compilation cache lives.

One rule for every entry point (``cli.main``, ``chip_smoke.py``): the
cache is placed from outside through ``JAX_COMPILATION_CACHE_DIR``, and
only when that is unset does the program pick a directory — a fixed
one, because a cache that moves is never hit.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir_from_env() -> str:
    """The operator's cache directory, or '' when none is set."""
    return os.environ.get(_ENV, "")


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set nothing is configured here:
    jax reads the variable itself. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — never a temp name, pid or time, so the
    next process finds what this one compiled. Call before the first
    compile."""
    path = cache_dir_from_env()
    if path:
        return path
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
