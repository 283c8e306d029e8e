"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``/``train`` ``main``,
``benchmarks/run.py``) call :func:`use_compile_cache` once at start-up;
library code never does.  The cache directory is part of the cache key, so
it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads the variable itself and that setting stands), otherwise
``.jax_cache/`` at the checkout root.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
