"""JAX's persistent compilation cache, placed by every entry point that may
run on a chip (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.serving_bench``).

A whole serving step of a model at published widths takes tens of seconds
to compile; with the cache, a second run of the same programs loads them
instead.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored). A fixed path: a cache directory that
# moves between runs never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``DEFAULT_DIR``.
    Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
