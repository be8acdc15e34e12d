"""Pallas TPU kernel: blocked centpath matmul (the MFBr Brandes action).

Computes ``C = F •_(⊗,g) B`` where (for the Brandes step ``B = A^T``)
``C.w(i,j) = max_k (F.w(i,k) - B(k,j))``   (inactive/no-edge -> -inf)
``C.p(i,j) = Σ_k F.p(i,k) · [tie at max]``
``C.c(i,j) = Σ_k [tie at max]``             (#children that reported)

Same VPU/VMEM structure as ``tropical_mm`` (the frontier arrives
transposed, so k walks the sublane axis); three accumulators (max-weight,
tie-summed partial centrality, tie count) stay resident in VMEM across the
k-sweep. Masking follows DESIGN.md §3: inactive frontier entries carry
``-inf`` and ``finite - inf = -inf`` loses the max-select, so no explicit
activity mask is needed inside the hot loop (weights are positive and the
frontier never holds ``+inf``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _kernel(fwt_ref, fpt_ref, b_ref, cw_ref, cp_ref, cc_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        cw_ref[...] = jnp.full_like(cw_ref, NEG_INF)
        cp_ref[...] = jnp.zeros_like(cp_ref)
        cc_ref[...] = jnp.zeros_like(cc_ref)

    def body(k, carry):
        accw, accp, accc = carry  # (bm, bn)
        # cand = F.w - B; -inf frontier or inf edge both yield -inf.
        fw_k = jnp.transpose(fwt_ref[pl.ds(k, 1), :])  # (bm, 1)
        cand = fw_k - b_ref[pl.ds(k, 1), :]
        cand = jnp.where(jnp.isnan(cand), NEG_INF, cand)  # (-inf) - (-w) guard
        pv = jnp.transpose(fpt_ref[pl.ds(k, 1), :])
        better = cand > accw
        tie = (cand == accw) & jnp.isfinite(cand)
        accp = jnp.where(better, jnp.broadcast_to(pv, accp.shape),
                         jnp.where(tie, accp + pv, accp))
        accc = jnp.where(better, jnp.ones_like(accc),
                         jnp.where(tie, accc + 1.0, accc))
        accw = jnp.maximum(accw, cand)
        return accw, accp, accc

    accw, accp, accc = jax.lax.fori_loop(
        0, bk, body, (cw_ref[...], cp_ref[...], cc_ref[...]))
    cw_ref[...] = accw
    cp_ref[...] = accp
    cc_ref[...] = accc


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def centpath_matmul_pallas(fwt: jax.Array, fpt: jax.Array, b: jax.Array, *,
                           bm: int = 128, bk: int = 128, bn: int = 128,
                           interpret: bool = False):
    """fwt/fpt: (n, nb), the frontier transposed; b: (n, n2).
    Returns (cw, cp, cc): (nb, n2)."""
    n, nb = fwt.shape
    n2 = b.shape[1]
    assert nb % bm == 0 and n % bk == 0 and n2 % bn == 0, (fwt.shape, b.shape)
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
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, n2), fwt.dtype),
            jax.ShapeDtypeStruct((nb, n2), fpt.dtype),
            jax.ShapeDtypeStruct((nb, n2), fwt.dtype),
        ],
        interpret=interpret,
    )(fwt, fpt, b)
