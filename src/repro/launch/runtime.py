"""Process set-up shared by every entry point: where JAX's persistent
compilation cache lives. Entry points call ``enable_compile_cache`` from
their ``main``; importing this module sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Environment variable JAX itself reads for the cache directory.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The fixed cache directory of this checkout (``.gitignore`` lists it).
#: A fixed path matters: the directory is part of every entry's key, so
#: a cache that moved between runs would never hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the
    root of the checkout.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
