"""JAX's persistent compilation cache, configured in one place.

The programs a user starts — ``chip_smoke.py``, ``repro.launch.serve`` and
``repro.launch.train`` — call ``enable()`` once, before their first
compile. Library imports and tests never call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
cache lives there; nothing here overrides it. Otherwise the cache goes to
the fixed directory ``<checkout>/.jax_cache`` (listed in ``.gitignore``):
the path is part of what a later run looks up, so it must not move.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
