"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, the ``main()`` of launch/train.py and
launch/serve.py) call ``enable_compile_cache`` once at start-up; importing
this module changes nothing, and tests never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already places the cache (JAX
    reads the variable itself), and nothing is set here.  Otherwise the cache
    is ``<checkout>/.jax_cache``: a fixed path, so every later run of the same
    checkout finds what an earlier one compiled.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
