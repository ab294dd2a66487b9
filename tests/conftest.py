"""Test bootstrap: make ``import repro`` work from a bare checkout.

Puts ``src/`` on sys.path so ``python -m pytest`` works without exporting
PYTHONPATH (the tier-1 command still sets it; both paths agree).
"""
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
_SRC = _ROOT / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

# Persistent XLA compilation cache: the suite is compile-dominated on CPU,
# so repeat runs (local dev, CI re-runs) skip most XLA work. Placed by the
# same rule as every entry point (JAX_COMPILATION_CACHE_DIR, else the
# checkout's gitignored .jax_cache/).
import jax  # noqa: E402

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

setup_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
