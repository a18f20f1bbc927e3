"""Where JAX keeps its persistent compilation cache for this checkout.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py`` and the
``launch/*.py`` scripts) calls :func:`use_compile_cache` before it
compiles anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it on its own and this sets nothing, so the cache can be placed from
outside.  Otherwise the cache goes to one fixed directory inside the
checkout (``.jax_cache/``, listed in ``.gitignore``), so a second run in
the same checkout finds what the first one wrote.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
