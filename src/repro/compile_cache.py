"""JAX's persistent compilation cache, placed by the entry points.

Entry points (``chip_smoke.py``, the ``benchmarks/`` mains) call
:func:`enable_compile_cache` once, before their first compile; importing a
library module never touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here.  Otherwise the cache
goes to one fixed directory in the checkout: the directory is part of what
a cached entry is found by, so a path that moved between runs (a temporary
or per-process one) would never be hit again.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache(root: "str | os.PathLike | None" = None) -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when set (left to JAX), else at ``<root>/.jax_cache`` (``root`` defaults
    to this checkout).  Returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = pathlib.Path(root if root is not None else CHECKOUT).resolve()
    path = path / ".jax_cache"
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
