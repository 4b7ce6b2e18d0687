"""Paths inside the source checkout, and the persistent compile cache.

Everything the program writes at run time lives under the checkout root
(``ROOT``), never under ``$HOME``: the autotune block cache at
``results/autotune/<backend>.json`` and JAX's persistent compilation cache
at ``.jax_cache/``.  A fixed path matters for the compile cache — the path
is part of what JAX keys entries on, so a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
