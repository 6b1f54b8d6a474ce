"""JAX's persistent compilation cache at one fixed place.

Called from each entry point's ``main`` (the spatter CLI, spatterd, the
benchmark runner, ``chip_smoke.py``) and never at import, so tests and
library users keep whatever cache setting they chose.  The directory is
part of every cache key, so it must not move between runs: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing else is changed), else ``.jax_cache`` at the root of
this checkout.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
