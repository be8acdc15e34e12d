"""Shared pytest setup.

* Puts ``src/`` on sys.path so the suite runs without ``PYTHONPATH=src``
  (and without requiring an installed wheel — CI installs the package, but
  a bare checkout works too).
* Hypothesis policy: CI bakes real hypothesis in (installed from
  ``requirements-dev.txt`` by the workflow), so on CI a missing install
  is a hard error — the deterministic ``tests/_hypothesis_fallback.py``
  shim must never silently water down the property tests there. On bare
  local runs without hypothesis, ``tests/`` goes on sys.path so the
  property tests' ``from _hypothesis_fallback import …`` fallback still
  collects and runs a fixed pseudo-random sweep.
"""
from __future__ import annotations

import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

try:
    import hypothesis  # noqa: F401
except ImportError:
    if os.environ.get("CI"):
        raise ImportError(
            "hypothesis is required in CI (pip install -r "
            "requirements-dev.txt); the _hypothesis_fallback shim is for "
            "bare local runs only")
    # Bare local run: make the fallback shim importable.
    if _HERE not in sys.path:
        sys.path.insert(0, _HERE)


@pytest.fixture
def restore_compile_cache():
    """Undo an entry point's ``enable_compile_cache`` after the test.

    Entry points turn the persistent compilation cache on for their
    process; called from a test, that would carry into every later test
    on the same worker (and change their compile times).
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()
