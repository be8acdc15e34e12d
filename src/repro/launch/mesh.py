"""Mesh construction from CLI specs.

Functions only, and jax is imported lazily *inside* them, so importing
this module never touches jax device state — callers that must set
``XLA_FLAGS`` (fake host devices) before jax initializes can import the
jax-free ``parse_mesh_spec`` first (``benchmarks/bc_approx.py`` does).

The ``pod`` axis is MFBC's replication factor c (DESIGN.md §4).

``mesh_from_spec("DxM" | "PxDxM")`` is the shared CLI entry point
(``launch.bc_run --mesh``, benchmarks): 2 sizes map (data, model),
3 map (pod, data, model), and the product must equal the visible jax
device count.
"""
from __future__ import annotations

from typing import Tuple


def parse_mesh_spec(spec: str) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """``"DxM"`` → ((D, M), (data, model)); ``"PxDxM"`` adds the pod axis.

    jax-free on purpose: callers validate the device count *before*
    anything imports jax (to set ``XLA_FLAGS``). Raises ``ValueError``
    on malformed specs.
    """
    try:
        dims = tuple(int(d) for d in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"mesh spec expects DxM or PxDxM (e.g. 2x4), "
                         f"got {spec!r}") from None
    if len(dims) == 2:
        names: Tuple[str, ...] = ("data", "model")
    elif len(dims) == 3:
        names = ("pod", "data", "model")
    else:
        raise ValueError(f"mesh spec expects 2 or 3 axis sizes, got {spec!r}")
    if min(dims) < 1:
        raise ValueError(f"mesh spec axis sizes must be positive, got "
                         f"{spec!r}")
    return dims, names


def mesh_from_spec(spec: str):
    """Build the jax mesh a CLI ``--mesh`` spec names, validating the
    axis-size product against the visible device count."""
    import jax

    dims, names = parse_mesh_spec(spec)
    need = 1
    for d in dims:
        need *= d
    n_dev = len(jax.devices())
    if need != n_dev:
        raise ValueError(f"mesh {spec!r} needs {need} devices, "
                         f"jax sees {n_dev}")
    return jax.make_mesh(dims, names)
