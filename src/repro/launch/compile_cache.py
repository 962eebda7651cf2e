"""JAX's persistent compilation cache, kept at one fixed place per checkout.

Every entry point (``chip_smoke.py``, ``examples/quickstart.py``,
``benchmarks/run.py`` and ``repro.launch.{dryrun,train,serve}``) calls
``enable_compile_cache()`` before its first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the helper
sets nothing else. Otherwise the cache lives at ``<checkout>/.jax_cache``
(gitignored): a fixed path, because the path is part of the cache key and
a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
