"""Where compiled programs are cached between runs.

Every entry point (`repro.launch.train`, `repro.supervise.run`, `bench/`,
`benchmarks/`, `chip_smoke.py`) calls `use_compile_cache()` before its
first compile.  If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads
it and nothing else is set.  Otherwise the cache lives at one fixed path inside
the checkout (`<repo>/.jax_cache`, git-ignored): the path is part of a
cache entry's key, so it must not be built from a temporary name, a
process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
