"""JAX's persistent compilation cache at a fixed path inside the checkout.

The entry points that compile at full size (`chip_smoke.py`,
`python -m benchmarks.run`, `python -m repro.serving.server`) call
`enable_compile_cache()` before their first compile, so their processes
share compiled executables.  The cache directory is part of each entry's
key, so it is a fixed path: never a temporary name, a process id or a
time.

    JAX_COMPILATION_CACHE_DIR set   -> JAX's own setting is left alone
    otherwise                       -> <checkout>/.jax_cache (gitignored)
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# src/repro/compile_cache.py -> the checkout root, two levels above src/
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  A `JAX_COMPILATION_CACHE_DIR` from the
    environment wins: JAX reads it itself, and nothing is overridden."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
