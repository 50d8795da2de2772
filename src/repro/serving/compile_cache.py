"""Where compiled programs persist between processes.

JAX's persistent compilation cache turns a restarted process's compiles
into disk loads. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets nothing. Otherwise the entry points
(``repro.launch.serve``, the serving benchmark's ``main`` and
``chip_smoke.py``) call :func:`configure_compile_cache` before their first
compile, and the cache lives at ``<repo>/.jax_cache``: a fixed path, so a
later process finds what an earlier one wrote. Library code (the prewarm
seams) never sets a cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> None:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already places it. Call before the
    process's first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
