"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``)
call ``enable_compile_cache`` once, before their first compile; no
library module does so as it is imported.
"""
from __future__ import annotations

import os

import jax

#: the checkout this module runs from (``<checkout>/src/repro/launch``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Where compiled programs are kept: ``JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads it itself, so it is left alone), else the
    fixed ``<checkout>/.jax_cache``. The path is part of each entry's
    key, so it never depends on the process, the time or a temporary
    directory. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
