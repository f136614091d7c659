"""JAX persistent compilation cache for the repo's entry points.

Every process entry point (``chip_smoke.py``, ``python -m
repro.launch.serve``, the ``benchmarks`` mains) calls
:func:`enable_compile_cache` once before it compiles anything, so a
second run of the same programs skips XLA compilation.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here.  Otherwise the cache lives at the fixed
path ``<checkout>/.jax_cache`` (gitignored): the cache key includes
nothing from the path, but a directory built from a temporary name, a
PID or the time would never be found again.  The checkout is where
``repro`` is imported from: ``<checkout>/src/repro`` (``PYTHONPATH=src``
or an editable install).  A ``repro`` installed elsewhere has no
checkout, and there the cache stays off unless the variable is set.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_PKG = Path(__file__).resolve().parent


def checkout_dir() -> Optional[Path]:
    """The checkout the ``repro`` package was imported from, or None
    when it does not sit at ``<checkout>/src/repro``."""
    return _PKG.parents[1] if _PKG.parent.name == "src" else None


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on; return the directory it uses
    (None: no checkout and no ``JAX_COMPILATION_CACHE_DIR``, cache off)."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    root = checkout_dir()
    if root is None:
        return None
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
