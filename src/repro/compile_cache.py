"""JAX's persistent compilation cache, placed for the entry points.

DROP's bucketed shapes compile many small executables on a cold start, so
the entry points (``chip_smoke.py``, ``repro.launch.drop_serve``,
``benchmarks.run``) turn the persistent cache on once, before their first
compile. Library modules never call this: importing them touches no config.
"""

from __future__ import annotations

import os
from pathlib import Path

# the cache key includes the directory, so it must not move between runs:
# a fixed path inside the checkout (listed in .gitignore), never a temp dir
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# DROP compiles many executables of 0.1-1 s (Halko stages, TLB tables,
# pairwise scans per bucket), under JAX's 1 s default floor; below ~0.1 s
# a cache read saves about what it costs
MIN_COMPILE_TIME_S = 0.1
# no size floor: small executables are the common case here
MIN_ENTRY_SIZE_BYTES = -1


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads it itself; no other is
    set), else ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_TIME_S
    )
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", MIN_ENTRY_SIZE_BYTES
    )
    return path
