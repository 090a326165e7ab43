"""Where JAX keeps its persistent compilation cache.

:func:`use_compile_cache` is called first thing by each entry point
(``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.scenarios``,
``benchmarks.run``), never at import.  When ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing is set here.  Otherwise the cache lives
at one fixed, git-ignored path in the checkout, ``<repo>/.jax_cache``: a path
derived from a temp name, a pid or the time would never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
