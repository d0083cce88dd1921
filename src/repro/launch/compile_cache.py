"""Persistent XLA compilation cache shared by the entry points.

``train``, ``serve`` and ``chip_smoke.py`` call :func:`enable_compile_cache`
before their first compile, so a process that compiles a program another
process already compiled reads it back instead.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed path inside the checkout (listed in .gitignore): the cache only
# hits when its directory stays put from one process to the next.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it: ``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`DEFAULT_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
