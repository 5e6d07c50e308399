"""Where JAX keeps its persistent compilation cache.

Called at the start of an entry point (``serve.main``, ``chip_smoke.py``),
never at import.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache goes to ``.jax_cache/``
at the root of the checkout: a fixed path, since the path is part of each
entry's key and a directory that moves never hits.  ``.gitignore`` lists it.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Make sure the persistent compilation cache is on; return its
    directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
