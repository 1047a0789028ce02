"""JAX's persistent compilation cache, placeable from outside.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
here touches the setting. Otherwise the cache lives in `.jax_cache/` at
the root of the checkout (gitignored): a fixed path, so every process of
the same checkout — a later run, or a second program of the same run —
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use. Call
    before the first compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
