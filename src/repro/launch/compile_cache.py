"""JAX persistent compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``bench/harness.py``) calls
``enable_compile_cache()`` before it compiles anything. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set here. Otherwise the cache lives at ``.jax_cache/`` in the
root of the checkout: a fixed path, because the path is part of what
lets a later process find an entry again. Tests never call this.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
