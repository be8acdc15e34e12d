"""Pallas TPU kernel: blocked multpath matmul (the MFBF Bellman-Ford action).

Computes ``C = F •_(⊕,f) A`` where
``C.w(i,j) = min_k (F.w(i,k) + A(k,j))`` and
``C.m(i,j) = Σ_k F.m(i,k) · [F.w(i,k) + A(k,j) == C.w(i,j)]``.

TPU adaptation notes (DESIGN.md §3): min-plus cannot run on the MXU, so
this is a VPU kernel. The value of the kernel is (a) HBM traffic — the
naive formulation materializes an (nb, k, n) candidate tensor in HBM per
k-block, while here candidates only ever exist as (bm, bn) vector tiles in
VMEM — and (b) keeping TWO accumulators (running min-weight + tie-summed
multiplicity) resident in VMEM across the whole k-sweep of the grid.

Grid layout: ``(i, j, k)`` with k innermost; the output BlockSpec index map
ignores k, so the same output tile is revisited and accumulated across the
k-sweep (the canonical Pallas reduction pattern). Inside the kernel an
``fori_loop`` sweeps the bk rows of the A tile one at a time, updating the
running (min, mult) pair with (bm, bn) vector ops — the 3D candidate block
is never materialized.

The frontier arrives transposed, ``(n, nb)``, so that k walks the sublane
axis of both operand tiles: row k of the A tile and row k of the Fᵀ tile
are dynamic sublane reads (``pl.ds``), which Mosaic lowers, and the Fᵀ row
turns into the (bm, 1) column the candidate tile broadcasts with one small
in-register transpose. Extracting column k of an F tile instead would be a
dynamic slice along the lane axis, which Mosaic does not lower.

Block sizes default to (bm, bk, bn) = (128, 128, 128): 4 f32 tiles of
128x128 = 256 KiB live VMEM, well under the ~16 MiB/core budget, and all
dims are multiples of the 8x128 VPU lane shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = float("inf")


def _kernel(fwt_ref, fmt_ref, a_ref, cw_ref, cm_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        cw_ref[...] = jnp.full_like(cw_ref, INF)
        cm_ref[...] = jnp.zeros_like(cm_ref)

    def body(k, carry):
        accw, accm = carry  # (bm, bn)
        # row k of the (bk, bm) Fᵀ tile as a (bm, 1) column; row k of A
        fw_k = jnp.transpose(fwt_ref[pl.ds(k, 1), :])
        cand = fw_k + a_ref[pl.ds(k, 1), :]  # (bm, bn)
        mult = jnp.transpose(fmt_ref[pl.ds(k, 1), :])
        better = cand < accw
        tie = (cand == accw) & jnp.isfinite(cand)
        accm = jnp.where(better, jnp.broadcast_to(mult, accm.shape),
                         jnp.where(tie, accm + mult, accm))
        accw = jnp.minimum(accw, cand)
        return accw, accm

    accw, accm = jax.lax.fori_loop(0, bk, body, (cw_ref[...], cm_ref[...]))
    cw_ref[...] = accw
    cm_ref[...] = accm


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def multpath_matmul_pallas(fwt: jax.Array, fmt: jax.Array, a: jax.Array, *,
                           bm: int = 128, bk: int = 128, bn: int = 128,
                           interpret: bool = False):
    """fwt/fmt: (n, nb), the frontier transposed; a: (n, n2).
    Returns (cw, cm): (nb, n2).

    Shapes must be multiples of the block sizes (the ops.py wrapper pads).
    """
    n, nb = fwt.shape
    n2 = a.shape[1]
    assert nb % bm == 0 and n % bk == 0 and n2 % bn == 0, (fwt.shape, a.shape)
    grid = (nb // bm, n2 // bn, n // bk)
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, n2), fwt.dtype),
            jax.ShapeDtypeStruct((nb, n2), fmt.dtype),
        ],
        interpret=interpret,
    )(fwt, fmt, a)
