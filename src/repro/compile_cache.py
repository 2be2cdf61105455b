"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`use_compile_cache` before its first compile.
The cache key includes the directory, so it lives at one fixed path: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX reads
that variable itself at import, so nothing is set here), and otherwise
``<checkout>/.jax_cache`` — never a temporary, per-process or per-run
directory, which would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
