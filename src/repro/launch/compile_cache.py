"""Persistent XLA compilation cache for the launchers.

The engine AOT-compiles one scan program per (family × template); a warm
cache turns those compiles into disk reads on the next run. Call
`enable()` before anything compiles.

Where the cache lives:
  * `JAX_COMPILATION_CACHE_DIR`, when set, and no other directory;
  * otherwise `<checkout>/.jax_cache` (gitignored). The path is fixed: it
    is part of the cache key, so a directory named after a process, a
    temporary file or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # The fused scan programs compile in about a second each, under JAX's
    # default one-second floor for what it persists.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
