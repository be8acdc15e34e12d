"""jit'd public wrappers for the Pallas kernels.

Handles (a) padding to block multiples with monoid identities so padding is
algebraically inert, (b) the transposed frontier layout the kernels read
(k along the sublane axis), (c) interpret mode on every backend but the
TPU (the interpreter executes the kernel body with plain JAX ops, so it
lowers to regular HLO on the CPU — used by tests; on the TPU the compiled
Mosaic kernel always runs), and (d) block-size selection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.centpath_mm import centpath_matmul_pallas
from repro.kernels.tropical_mm import multpath_matmul_pallas

INF = jnp.inf


def _interpret() -> bool:
    """Interpret mode is for backends without Mosaic (the CPU) only."""
    return jax.default_backend() != "tpu"


def _pad_to(x, rows, cols, fill):
    r, c = x.shape
    return jnp.pad(x, ((0, rows - r), (0, cols - c)), constant_values=fill)


def _pick_block(dim: int, pref: int) -> int:
    """Largest power-of-two block <= pref that keeps padding sane.

    With the default ``pref`` of 128 every block is either 128 (a multiple
    of the (8, 128) TPU tile) or, for a dim below 64, a single block that
    covers the whole padded dim — the two shapes Mosaic accepts.
    """
    b = pref
    while b > 8 and dim < b // 2:
        b //= 2
    return b


def multpath_matmul(fw: jax.Array, fm: jax.Array, a: jax.Array, *,
                    bm: int = 128, bk: int = 128, bn: int = 128):
    """Padded/blocked multpath matmul. fw/fm: (nb, n); a: (n, n2)."""
    nb, n = fw.shape
    n2 = a.shape[1]
    bm = _pick_block(nb, bm)
    bk = _pick_block(n, bk)
    bn = _pick_block(n2, bn)
    NB, N, N2 = -(-nb // bm) * bm, -(-n // bk) * bk, -(-n2 // bn) * bn
    fw_p = _pad_to(fw, NB, N, INF)
    fm_p = _pad_to(fm, NB, N, 0.0)
    a_p = _pad_to(a, N, N2, INF)
    cw, cm = multpath_matmul_pallas(fw_p.T, fm_p.T, a_p, bm=bm, bk=bk, bn=bn,
                                    interpret=_interpret())
    return cw[:nb, :n2], cm[:nb, :n2]


def centpath_matmul(fw: jax.Array, fp: jax.Array, b: jax.Array, *,
                    bm: int = 128, bk: int = 128, bn: int = 128):
    """Padded/blocked centpath matmul. fw/fp: (nb, n); b: (n, n2) (= A^T)."""
    nb, n = fw.shape
    n2 = b.shape[1]
    bm = _pick_block(nb, bm)
    bk = _pick_block(n, bk)
    bn = _pick_block(n2, bn)
    NB, N, N2 = -(-nb // bm) * bm, -(-n // bk) * bk, -(-n2 // bn) * bn
    fw_p = _pad_to(fw, NB, N, -INF)
    fp_p = _pad_to(fp, NB, N, 0.0)
    b_p = _pad_to(b, N, N2, INF)
    cw, cp, cc = centpath_matmul_pallas(fw_p.T, fp_p.T, b_p, bm=bm, bk=bk,
                                        bn=bn, interpret=_interpret())
    return cw[:nb, :n2], cp[:nb, :n2], cc[:nb, :n2]
