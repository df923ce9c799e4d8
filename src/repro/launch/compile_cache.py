"""Persistent XLA compilation cache for the launchers and `chip_smoke.py`.

Call `enable_compile_cache()` once at program start-up (never at import).
Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
sets nothing. Otherwise the cache lives at one fixed, gitignored path in
the checkout, `<repo>/.jax_cache`: the directory is part of each entry's
key, so a path that moved between runs (temp, pid or time derived) would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
