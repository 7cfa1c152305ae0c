"""Where compiled programs persist between runs.

JAX keys its persistent compilation cache by the cache directory too, so
a directory that moves never hits.  ``JAX_COMPILATION_CACHE_DIR``, when
set, is the deployment's choice and JAX reads it on its own; otherwise
the cache lives at a fixed ``.jax_cache/`` in the checkout root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/cache.py -> parents[3]
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call from an entry point's ``main()``, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
