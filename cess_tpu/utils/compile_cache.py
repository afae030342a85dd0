"""The persistent XLA compile cache: one fixed directory per checkout.

The fused verify program and the Pallas kernels take minutes to compile
cold (proof/fused.py), so every entry point that compiles them — the
test suite, the benchmark, __graft_entry__.py, chip_smoke.py — shares
one cache.  JAX reads `JAX_COMPILATION_CACHE_DIR` itself: when it is set,
nothing here touches the directory.  Otherwise the cache lives at
`<checkout>/.jax_cache` (gitignored), a path that never depends on a
temp name, a pid or the clock, so a later run finds what an earlier one
wrote.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Must run
    before the first jit compiles anything."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    # A Pallas kernel's serialized body carries the source locations of
    # its trace (file paths and line numbers, up to this many frames),
    # and the cache key hashes that body: with locations in, the fused
    # verify program (minutes to compile) misses the cache from another
    # checkout path or after an edit that only moves lines.
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return env or str(CACHE_DIR)
