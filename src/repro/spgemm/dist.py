"""Distributed SpGEMM variants as shard_map programs (paper §5.2 on TPU).

``spgemm(A, B, mesh, plan, semiring)`` computes the generalized product
``C(i,j) = ⊕_k f(A(i,k), B(k,j))`` for (pytree-valued) operands
``A: (m, k)`` and ``B: (k, n)`` using the decomposition named by ``plan``.

Implemented variants (paper labels; L/R below = left/right operand):

* ``1d_a``  — replicate L via all-gather; R and C column-sharded.
* ``1d_b``  — replicate R; L and C row-sharded.
* ``1d_c``  — shard the contraction dim; ⊕-reduce C (paper's variant C).
* ``2d_ab`` — SUMMA: gather L along grid columns and R along grid rows.
* ``2d_ac`` — gather L, ⊕-reduce-scatter C (R stationary).
* ``2d_bc`` — gather R, ⊕-reduce-scatter C (L stationary).
* ``3d_l_*``, ``3d_r_*``, ``3d_c_*`` — 1D replication of L / R /
  contraction-split over the first axis, nested with any 2D variant on the
  remaining two axes (the paper's nine-variant family; the Theorem 5.1 BC
  configuration is ``3d_r_ac``: adjacency replicated over the pod axis,
  frontier gathered, output reduce-scattered).

Each variant documents its input/output layouts as PartitionSpecs; the
byte cost of every collective matches ``repro.spgemm.cost_model`` (tested
by parsing compiled HLO in ``tests/test_spgemm*.py``).

CTF correspondence: CTF redistributes operands between processor grids at
runtime; under XLA SPMD the "redistribution" is the resharding XLA inserts
to satisfy ``in_specs`` — the autotuner therefore prefers plans whose input
layout matches the caller's persistent layout (e.g. the adjacency stays in
its ``2d_*`` layout across all MFBC iterations).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.spgemm.semiring import GeneralizedSemiring, arithmetic

Tree = Any


@dataclasses.dataclass(frozen=True)
class Plan:
    """A decomposition choice: variant name + mesh axis assignment.

    axes: (q,) for 1d, (r, c) for 2d, (p1, r, c) for 3d.
    """

    variant: str
    axes: Tuple[str, ...]

    def __post_init__(self):
        n_axes = {"1": 1, "2": 2, "3": 3}[self.variant[0]]
        assert len(self.axes) == n_axes, (self.variant, self.axes)


def _gather(x: Tree, axis_name: str, dim: int) -> Tree:
    return jax.tree.map(
        lambda v: jax.lax.all_gather(v, axis_name, axis=dim, tiled=True), x)


def _reduce_slice(x: Tree, axis_name: str, dim: int,
                  sr: GeneralizedSemiring) -> Tree:
    """⊕-reduce over an axis, then keep this shard's slice of ``dim``.

    For the arithmetic monoid this is a true ``psum_scatter``; general
    monoids reduce (pmin/pmax + psum pair) then slice.
    """
    if sr.name == "arith":
        return jax.tree.map(
            lambda v: jax.lax.psum_scatter(v, axis_name, scatter_dimension=dim,
                                           tiled=True), x)
    red = sr.axis_reduce(x, axis_name)
    idx = jax.lax.axis_index(axis_name)
    sz = jax.lax.axis_size(axis_name)

    def slc(v):
        blk = v.shape[dim] // sz
        return jax.lax.dynamic_slice_in_dim(v, idx * blk, blk, axis=dim)

    return jax.tree.map(slc, red)


# --------------------------------------------------------------------------
# Layout tables: input/output PartitionSpecs per variant.
# --------------------------------------------------------------------------


def plan_specs(plan: Plan) -> Tuple[P, P, P]:
    """(spec_L, spec_R, spec_C) for the global operands under ``plan``."""
    v, ax = plan.variant, plan.axes
    if v == "1d_a":
        (q,) = ax
        return P(None, q), P(None, q), P(None, q)
    if v == "1d_b":
        (q,) = ax
        return P(q, None), P(q, None), P(q, None)
    if v == "1d_c":
        (q,) = ax
        return P(None, q), P(q, None), P(None, None)
    if v == "2d_ab":
        r, c = ax
        return P(r, c), P(r, c), P(r, c)
    if v == "2d_ac":
        r, c = ax
        return P(c, r), P(r, c), P(r, c)
    if v == "2d_bc":
        r, c = ax
        return P(r, c), P(c, r), P(r, c)
    if v.startswith("3d_"):
        _, x, yz = v.split("_")
        inner = plan_specs(Plan(f"2d_{yz}", ax[1:]))
        p1 = ax[0]
        sL, sR, sC = inner

        def stack(spec: P, dim: int) -> P:
            parts = [spec[0], spec[1]]
            cur = parts[dim]
            parts[dim] = (p1,) + ((cur,) if isinstance(cur, str) else tuple(cur or ()))
            return P(*parts)

        if x == "l":  # L replicated over p1; R, C split their free dim (n)
            return sL, stack(sR, 1), stack(sC, 1)
        if x == "r":  # R replicated over p1; L, C split their free dim (m)
            return stack(sL, 0), sR, stack(sC, 0)
        if x == "c":  # contraction split over p1
            return stack(sL, 1), stack(sR, 0), sC
    raise ValueError(f"unknown variant {plan.variant}")


# --------------------------------------------------------------------------
# Local (per-shard) programs.
# --------------------------------------------------------------------------


def _local_1d_a(plan, sr, a, b):
    (q,) = plan.axes
    a_full = _gather(a, q, 1)  # bytes ≈ nnz(L): paper W_A
    return sr.block_mm(a_full, b)


def _local_1d_b(plan, sr, a, b):
    (q,) = plan.axes
    b_full = _gather(b, q, 0)  # bytes ≈ nnz(R): paper W_B
    return sr.block_mm(a, b_full)


def _local_1d_c(plan, sr, a, b):
    (q,) = plan.axes
    c_part = sr.block_mm(a, b)
    return sr.axis_reduce(c_part, q)  # bytes ≈ nnz(C): paper W_C


def _local_2d_ab(plan, sr, a, b):
    r, c = plan.axes
    a_row = _gather(a, c, 1)  # bytes ≈ nnz(L)/p_r
    b_col = _gather(b, r, 0)  # bytes ≈ nnz(R)/p_c
    return sr.block_mm(a_row, b_col)


def _local_2d_ac(plan, sr, a, b):
    r, c = plan.axes
    a_full = _gather(a, c, 0)  # L arrives (m, k/p_r): bytes ≈ nnz(L)/p_r
    c_part = sr.block_mm(a_full, b)  # (m, n/p_c), partial over r
    return _reduce_slice(c_part, r, 0, sr)  # bytes ≈ nnz(C)/p_c


def _local_2d_bc(plan, sr, a, b):
    r, c = plan.axes
    b_full = _gather(b, r, 1)  # R arrives (k/p_c, n): bytes ≈ nnz(R)/p_c
    c_part = sr.block_mm(a, b_full)  # (m/p_r, n), partial over c
    return _reduce_slice(c_part, c, 1, sr)  # bytes ≈ nnz(C)/p_r


_LOCAL = {
    "1d_a": _local_1d_a,
    "1d_b": _local_1d_b,
    "1d_c": _local_1d_c,
    "2d_ab": _local_2d_ab,
    "2d_ac": _local_2d_ac,
    "2d_bc": _local_2d_bc,
}


def _local_3d(plan, sr, a, b):
    _, x, yz = plan.variant.split("_")
    inner = Plan(f"2d_{yz}", plan.axes[1:])
    p1 = plan.axes[0]
    if x in ("l", "r"):
        # The replicated operand is already identical across p1 (its spec
        # omits p1); inner 2D runs independently per p1 slice.
        return _LOCAL[inner.variant](inner, sr, a, b)
    # x == "c": contraction split over p1 -> inner product is partial.
    c_part = _LOCAL[inner.variant](inner, sr, a, b)
    return sr.axis_reduce(c_part, p1)


def spgemm(a: Tree, b: Tree, mesh: Mesh, plan: Plan,
           sr: GeneralizedSemiring = arithmetic,
           out_spec: Optional[P] = None) -> Tree:
    """Distributed generalized matmul. See module docstring for layouts."""
    spec_a, spec_b, spec_c = plan_specs(plan)
    local = _local_3d if plan.variant.startswith("3d_") else _LOCAL[plan.variant]

    fn = shard_map(
        partial(local, plan, sr),
        mesh=mesh,
        in_specs=(spec_a, spec_b),
        out_specs=spec_c,
        check_vma=False,
    )
    out = fn(a, b)
    if out_spec is not None:
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, out_spec))
    return out


def replicate_adjacency(b: Tree, mesh: Mesh, pod_axis: str) -> Tree:
    """One-time replication of a persistent operand across the pod axis.

    The Theorem 5.1 proof amortizes the adjacency broadcast across all
    (up to d) products and all n/n_b batches; callers do it once here and
    then run ``3d_r_*`` plans whose R-spec omits the pod axis.
    """
    spec = P(*([None] * jax.tree.leaves(b)[0].ndim))
    return jax.lax.with_sharding_constraint(
        b, jax.sharding.NamedSharding(mesh, spec))
