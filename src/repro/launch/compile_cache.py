"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (the serve launcher, ``chip_smoke.py`` and
the test suite): if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no other directory is set in code; otherwise the cache lives
in ``<checkout>/.jax_cache``, a fixed path, so repeat runs hit it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/launch`` -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
