"""Where JAX keeps its persistent compilation cache — one rule for every
entry point (``server.main``, ``serve.main``, ``chip_smoke.py``,
``benchmarks/bench_engine.py``).

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored).  The path is part of what makes a
cache hit: a directory built from a temporary name, a pid or the time
would never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Touches no backend."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
