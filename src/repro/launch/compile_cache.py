"""Where JAX's persistent compilation cache lives for the entry points.

A cold start at published widths spends minutes compiling. The cache
directory is part of the cache key, so it must not move between runs: a
directory named after a temporary path, a pid or a time never hits.

The key includes each program's metadata (its ``jax.named_scope`` paths,
file names and lines). Without it, two programs that differ only in their
scopes share one entry, and whichever compiled first lends its op_names
to the other: a device trace of the second would then be attributed to
the first one's scopes. So the checkout must not move either.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — this file sits at <checkout>/src/repro/launch/
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and no directory is set here; otherwise the cache goes to the fixed
    :data:`DEFAULT_DIR` inside the checkout. Either way the key includes
    the program's metadata."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
