"""Persistent XLA compilation cache wiring.

Cold compiles dominate the first call of every rollout program: the
scanned window and the batched replay.  JAX's persistent compilation
cache keys executables by (HLO, jaxlib version, backend, flags), so a
warm directory turns those compiles into disk reads.  The directory is part of what a later run must find again, so it
is never a temporary or per-process name.

Entry points (``chip_smoke.py``, ``benchmarks/run.py`` and each bench's
``__main__``) call ``enable_persistent_cache()`` before the first jitted
dispatch.  Library code and the tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore): src/repro/launch/cache.py
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise
    ``DEFAULT_CACHE_DIR`` inside the checkout; no other path is set.
    Returns the directory in use.  Thresholds are zeroed so even the
    small scan graphs the rollout engine compiles are cached.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
