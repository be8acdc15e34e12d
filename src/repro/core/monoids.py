"""Multpath / centpath monoid algebra (paper Sections 3, 4.1.1, 4.2.1).

A *multpath* is a tuple ``(w, m)``: path weight + multiplicity. The monoid
``(M, ⊕)`` keeps the smaller weight and sums multiplicities on ties. The
Bellman-Ford *action* is ``f((w, m), a) = (w + a, m)``.

A *centpath* is a tuple ``(w, p, c)``: weight + partial centrality factor +
counter. The monoid ``(C, ⊗)`` keeps the **larger** weight and sums ``p``
and ``c`` on ties. The Brandes action is ``g((w, p, c), a) = (w - a, p, c)``.

TPU adaptation (see DESIGN.md §3): frontiers are dense-in-structure,
sparse-in-value. A multpath entry is *inactive* when ``(w, m) = (inf, 0)``;
a centpath entry is inactive when ``w = -inf``. CTF keeps nulls structurally
absent; we mask them explicitly, because IEEE ``inf - a = inf`` would
otherwise win the centpath max-selection.

Two relaxation regimes are provided for each action:

* ``*_relax_dense``  — blocked generalized matmul against a dense ``(n, n)``
  adjacency (``inf`` off-structure). ``C(i,j) = ⊕_k f(T(i,k), A(k,j))``.
  This is the jnp oracle for the Pallas kernels in ``repro.kernels``.
* ``*_relax_coo``    — edge-list relaxation via ``segment_min/max`` + a
  tie-masked ``segment_sum`` (the TPU-native sparse idiom).
* ``*_relax_csr``    — frontier-compacted relaxation: the active entries
  of ``F`` are compacted into a static-capacity slot buffer
  (``jnp.nonzero(..., size=cap)``), only their incident CSR arc ranges
  are expanded, and candidates are scattered with the same segment ops —
  per-iteration work tracks the maximal frontier instead of E.

Equality of float path weights is exact (paper assumes exact arithmetic;
integer-valued float32 weights are exact up to 2**24).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.inf


class Multpath(NamedTuple):
    w: jax.Array  # weights, inactive = +inf
    m: jax.Array  # multiplicities, inactive = 0


class Centpath(NamedTuple):
    w: jax.Array  # weights, inactive = -inf
    p: jax.Array  # partial centrality factor
    c: jax.Array  # counter (number of contributing children on ties)


def multpath_identity(shape, dtype=jnp.float32) -> Multpath:
    return Multpath(jnp.full(shape, INF, dtype), jnp.zeros(shape, dtype))


def centpath_identity(shape, dtype=jnp.float32) -> Centpath:
    return Centpath(jnp.full(shape, -INF, dtype), jnp.zeros(shape, dtype),
                    jnp.zeros(shape, dtype))


def multpath_combine(x: Multpath, y: Multpath) -> Multpath:
    """Elementwise ⊕: min weight, sum multiplicities on exact ties."""
    w = jnp.minimum(x.w, y.w)
    tie = (x.w == y.w) & jnp.isfinite(x.w)
    m = jnp.where(x.w < y.w, x.m, jnp.where(tie, x.m + y.m, y.m))
    return Multpath(w, m)


def centpath_combine(x: Centpath, y: Centpath) -> Centpath:
    """Elementwise ⊗: max weight, sum p and c on exact ties."""
    w = jnp.maximum(x.w, y.w)
    tie = (x.w == y.w) & jnp.isfinite(x.w)
    p = jnp.where(x.w > y.w, x.p, jnp.where(tie, x.p + y.p, y.p))
    c = jnp.where(x.w > y.w, x.c, jnp.where(tie, x.c + y.c, y.c))
    return Centpath(w, p, c)


# ---------------------------------------------------------------------------
# Dense regime: blocked generalized matmul.
# ---------------------------------------------------------------------------


def _mp_block(Fw, Fm, Ablk):
    """min-plus with multiplicities over one k-block.

    Fw, Fm: (nb, bk); Ablk: (bk, n) -> (nb, n) pair.
    """
    cand = Fw[:, :, None] + Ablk[None, :, :]  # (nb, bk, n); inf + x = inf
    w = jnp.min(cand, axis=1)
    tie = (cand == w[:, None, :]) & jnp.isfinite(cand)
    m = jnp.sum(jnp.where(tie, Fm[:, :, None], 0.0), axis=1)
    return w, m


def multpath_relax_dense(F: Multpath, A: jax.Array, *, block: int = 256,
                         unroll: bool = False) -> Multpath:
    """``C = F •_(⊕,f) A``: C(s,v) = ⊕_u f(F(s,u), A(u,v)).

    F.w/F.m: (nb, k); A: (k, n_out) with inf off-structure. Returns
    (nb, n_out). Blocked over the contraction dim to keep the
    (nb, bk, n_out) intermediate bounded.
    """
    nb, k = F.w.shape
    n_out = A.shape[1]
    block = min(block, k)
    nblk = -(-k // block)
    kpad = nblk * block
    Fw = jnp.pad(F.w, ((0, 0), (0, kpad - k)), constant_values=INF)
    Fm = jnp.pad(F.m, ((0, 0), (0, kpad - k)))
    Ap = jnp.pad(A, ((0, kpad - k), (0, 0)), constant_values=INF)
    Fw = Fw.reshape(nb, nblk, block)
    Fm = Fm.reshape(nb, nblk, block)
    Ap = Ap.reshape(nblk, block, n_out)

    def step(acc, blk):
        fw, fm, ab = blk
        w, m = _mp_block(fw, fm, ab)
        return multpath_combine(acc, Multpath(w, m)), None

    init = multpath_identity((nb, n_out), F.w.dtype)
    if unroll:  # exact cost accounting for the dry-run (scan counts once)
        acc = init
        for i in range(nblk):
            acc, _ = step(acc, (Fw[:, i], Fm[:, i], Ap[i]))
        return acc
    out, _ = jax.lax.scan(step, init,
                          (jnp.moveaxis(Fw, 1, 0), jnp.moveaxis(Fm, 1, 0), Ap))
    return out


def _cp_block(Fw, Fp, Bblk):
    """max-select with p/c tie sums over one k-block.

    Fw, Fp: (nb, bk); Bblk: (bk, n). Inactive F entries carry w = -inf.
    cand(s, v) = F.w(s, u) - B(u, v); inactive or no-edge -> -inf.
    """
    cand = Fw[:, :, None] - Bblk[None, :, :]
    cand = jnp.where(jnp.isfinite(Fw)[:, :, None] & jnp.isfinite(Bblk)[None, :, :],
                     cand, -INF)
    w = jnp.max(cand, axis=1)
    tie = (cand == w[:, None, :]) & jnp.isfinite(cand)
    p = jnp.sum(jnp.where(tie, Fp[:, :, None], 0.0), axis=1)
    c = jnp.sum(jnp.where(tie, 1.0, 0.0), axis=1)
    return w, p, c


def centpath_relax_dense(F: Centpath, B: jax.Array, *, block: int = 256,
                         unroll: bool = False) -> Centpath:
    """``C = F •_(⊗,g) B`` with contraction over B's first axis.

    For the Brandes step the caller passes ``B = A.T`` so that
    ``C(s, v) = ⊗_u g(F(s, u), A(v, u))`` — contributions flow from
    SP-DAG children ``u`` back to predecessors ``v``.
    """
    nb, k = F.w.shape
    n_out = B.shape[1]
    block = min(block, k)
    nblk = -(-k // block)
    kpad = nblk * block
    Fw = jnp.pad(F.w, ((0, 0), (0, kpad - k)), constant_values=-INF)
    Fp = jnp.pad(F.p, ((0, 0), (0, kpad - k)))
    Bp = jnp.pad(B, ((0, kpad - k), (0, 0)), constant_values=INF)
    Fw = Fw.reshape(nb, nblk, block)
    Fp = Fp.reshape(nb, nblk, block)
    Bp = Bp.reshape(nblk, block, n_out)

    def step(acc, blk):
        fw, fp, bb = blk
        w, p, c = _cp_block(fw, fp, bb)
        return centpath_combine(acc, Centpath(w, p, c)), None

    init = centpath_identity((nb, n_out), F.w.dtype)
    if unroll:
        acc = init
        for i in range(nblk):
            acc, _ = step(acc, (Fw[:, i], Fp[:, i], Bp[i]))
        return acc
    out, _ = jax.lax.scan(step, init,
                          (jnp.moveaxis(Fw, 1, 0), jnp.moveaxis(Fp, 1, 0), Bp))
    return out


def count_sp_children_dense(Tw: jax.Array, A: jax.Array, *, block: int = 256
                            ) -> jax.Array:
    """c0(s, v) = #{u : T(s,v).w + A(v,u) == T(s,u).w, both finite}.

    The number of shortest-path-DAG children of v (vertices whose shortest
    path's last hop leaves v). Blocked over v's out-neighborhood.
    """
    nb, n = Tw.shape
    block = min(block, n)
    nblk = -(-n // block)
    npad = nblk * block
    Ap = jnp.pad(A, ((0, 0), (0, npad - n)), constant_values=INF)

    def step(acc, ub):
        Ablk = jax.lax.dynamic_slice_in_dim(Ap, ub * block, block, axis=1)  # (n, bk)
        Twu = jax.lax.dynamic_slice_in_dim(
            jnp.pad(Tw, ((0, 0), (0, npad - n)), constant_values=INF),
            ub * block, block, axis=1)  # (nb, bk)
        # cand(s, v, u) = Tw(s, v) + A(v, u)
        cand = Tw[:, :, None] + Ablk[None, :, :]
        hit = (cand == Twu[:, None, :]) & jnp.isfinite(cand)
        return acc + jnp.sum(hit, axis=2), None

    acc0 = jnp.zeros((nb, n), jnp.int32)
    out, _ = jax.lax.scan(step, acc0, jnp.arange(nblk))
    return out


# ---------------------------------------------------------------------------
# COO (sparse) regime: segment-op relaxations.
# ---------------------------------------------------------------------------
#
# Each indexed pass over the arcs costs about the same per index whatever
# the width of the row it moves (on TPU v5e an (E, 32) segment_sum takes
# what an (E, 16) one does), so every relax below makes one gather per
# distinct index vector (fields read at the same index are stacked into
# one table first) and one scatter per reducer (the p and c tie sums share
# a window). The candidates and reductions are those of the per-field
# formulation, so results are bitwise the same.
#
# ``sorted_seg`` tells XLA that ``seg`` is non-decreasing
# (``indices_are_sorted`` on every scatter over it), which lets it skip
# the sort it otherwise makes in each call. Only a container whose arcs
# are stored in segment order sets it (``CsrAdj``, which checks the order
# when it is built): on unsorted ids the flag gives wrong answers silently.


def _gather_cols(rows, idx: jax.Array):
    """``[r[:, idx] for r in rows]`` with one gather: the (nb, n) fields
    are stacked into a (k·nb, n) table, gathered once, then split."""
    nb = rows[0].shape[0]
    got = jnp.concatenate(rows, axis=0)[:, idx]
    return [got[i * nb:(i + 1) * nb] for i in range(len(rows))]


def _multpath_scatter(Fw: jax.Array, Fm: jax.Array, wa: jax.Array,
                      seg: jax.Array, n: int, *, sorted_seg: bool = False
                      ) -> Multpath:
    """Reduce the (nb, E) gathered frontier into ``seg``'s segments:
    min of ``Fw + wa``, then the tie-masked multiplicity sum."""
    cand = Fw + wa[None, :]  # (nb, E); inf + x = inf
    minw = jax.ops.segment_min(cand.T, seg, num_segments=n,
                               indices_are_sorted=sorted_seg).T  # (nb, n)
    tie = (cand == minw[:, seg]) & jnp.isfinite(cand)
    m = jax.ops.segment_sum(jnp.where(tie, Fm, 0.0).T, seg,
                            num_segments=n,
                            indices_are_sorted=sorted_seg).T
    # segment_min of empty segments yields +inf-ish max value for floats;
    # normalize: entries with zero multiplicity are inactive.
    minw = jnp.where(m > 0, minw, INF)
    return Multpath(minw, m)


def _centpath_scatter(Fw: jax.Array, Fp: jax.Array, wa: jax.Array,
                      alive: jax.Array, seg: jax.Array, n: int, *,
                      sorted_seg: bool = False) -> Centpath:
    """Reduce the (nb, E) gathered frontier into ``seg``'s segments:
    max of ``Fw - wa`` over live arcs, then the p and c tie sums in one
    scatter (c's update is the tie mask: every contributing child
    counts once)."""
    cand = jnp.where(alive[None, :] & jnp.isfinite(Fw), Fw - wa[None, :],
                     -INF)  # (nb, E)
    maxw = jax.ops.segment_max(cand.T, seg, num_segments=n,
                               indices_are_sorted=sorted_seg).T  # (nb, n)
    tie = (cand == maxw[:, seg]) & jnp.isfinite(cand)
    nb = Fw.shape[0]
    pc = jax.ops.segment_sum(
        jnp.concatenate([jnp.where(tie, Fp, 0.0), jnp.where(tie, 1.0, 0.0)]).T,
        seg, num_segments=n, indices_are_sorted=sorted_seg).T  # (2·nb, n)
    p, c = pc[:nb], pc[nb:]
    maxw = jnp.where(c > 0, maxw, -INF)
    return Centpath(maxw, p, c)


def multpath_relax_coo(F: Multpath, src: jax.Array, dst: jax.Array,
                       w: jax.Array, n: int, *, sorted_seg: bool = False
                       ) -> Multpath:
    """Edge-list version of ``multpath_relax_dense``.

    src/dst/w: (E,) padded COO arcs (padding arcs carry w = inf).
    F.w/F.m: (nb, n). Cost O(nb * E); chunk over nb upstream if needed.
    Segments over ``dst``; ``sorted_seg``: ``dst`` is non-decreasing.
    """
    Fw, Fm = _gather_cols([F.w, F.m], src)
    return _multpath_scatter(Fw, Fm, w, dst, n, sorted_seg=sorted_seg)


def centpath_relax_coo(F: Centpath, src: jax.Array, dst: jax.Array,
                       w: jax.Array, n: int, *, sorted_seg: bool = False
                       ) -> Centpath:
    """Edge-list Brandes action: contributions flow dst -> src.

    For arc (v -> u, a): cand(s, v) over children u: F.w(s, u) - a.
    Segment over ``src`` (the predecessor side); ``sorted_seg``: ``src``
    is non-decreasing.
    """
    Fw, Fp = _gather_cols([F.w, F.p], dst)
    return _centpath_scatter(Fw, Fp, w, jnp.isfinite(w), src, n,
                             sorted_seg=sorted_seg)


def count_sp_children_coo(Tw: jax.Array, src: jax.Array, dst: jax.Array,
                          w: jax.Array, n: int, *, sorted_seg: bool = False
                          ) -> jax.Array:
    """COO version of ``count_sp_children_dense``: segment over ``src``
    (``sorted_seg``: ``src`` is non-decreasing)."""
    cand = Tw[:, src] + w[None, :]  # (nb, E)
    hit = (cand == Tw[:, dst]) & jnp.isfinite(cand)
    return jax.ops.segment_sum(hit.astype(jnp.int32).T, src,
                               num_segments=n,
                               indices_are_sorted=sorted_seg).T


# ---------------------------------------------------------------------------
# Frontier-compacted CSR regime: work tracks the maximal frontier.
# ---------------------------------------------------------------------------


def _compact_cols(mask: jax.Array, indptr: jax.Array, vcap: int):
    """Compact the frontier's active *columns* into ``vcap`` slots.

    mask: (nb, n) bool frontier occupancy. A column (vertex) is active
    when any batch row holds it — the union frontier. Compacting columns
    instead of (row, vertex) pairs keeps the batch axis contiguous, so
    the relax below runs the same SIMD-friendly 2D segment ops as the
    COO kernels, just over the frontier's incident arc set. Returns
    (u, first, offs): per-slot vertex id, its first arc id, and the
    inclusive cumsum of per-slot arc degrees (``offs[-1]`` = total
    incident arcs). Slots past the population carry degree 0, so they
    own no arc range.
    """
    n = mask.shape[1]
    cols = jnp.nonzero(jnp.any(mask, axis=0), size=vcap, fill_value=n)[0]
    valid = cols < n
    u = jnp.where(valid, cols, 0).astype(jnp.int32)
    first = indptr[u]
    deg = jnp.where(valid, indptr[u + 1] - first, 0)
    offs = jnp.cumsum(deg)
    return u, first, offs


def _expand_edges(u: jax.Array, first: jax.Array, offs: jax.Array,
                  ecap: int):
    """Expand compacted slots into ``ecap`` load-balanced arc slots.

    Owner assignment is a scatter of each populated slot's start offset
    followed by a cumulative max — two linear passes over ``ecap``, no
    per-arc binary search. The owner's vertex and arc base come from one
    gather of a per-slot ``[u, first - start]`` table. Returns (uj, eid,
    live); dead slots (``pos >= offs[-1]``) are masked.
    """
    vcap = u.shape[0]
    pos = jnp.arange(ecap, dtype=offs.dtype)
    starts = jnp.concatenate([jnp.zeros((1,), offs.dtype), offs[:-1]])
    slots = jnp.arange(vcap, dtype=jnp.int32)
    # Degree-0 slots share a start with their successor; dropping them
    # keeps the cummax from handing their (empty) range to the wrong owner.
    tgt = jnp.where(offs > starts, starts, ecap)
    owner = jnp.zeros((ecap,), jnp.int32).at[tgt].max(slots, mode="drop")
    j = jax.lax.cummax(owner)
    live = pos < offs[-1]
    tab = jnp.stack([u, first - starts], axis=1)[j]  # (ecap, 2)
    eid = jnp.where(live, pos + tab[:, 1], 0)
    return tab[:, 0], eid.astype(jnp.int32), live


def arc_table(other: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(E, 2) int32 ``[other end, bit pattern of w]`` of CSR-sorted arcs,
    built once on the host: what a compacted relax reads at an arc id,
    in one gather (``_expand_arcs``)."""
    return np.stack([np.asarray(other, np.int32),
                     np.asarray(w, np.float32).view(np.int32)], axis=1)


def _expand_arcs(active: jax.Array, indptr: jax.Array, arcs: jax.Array,
                 vcap: int, ecap: int):
    """Arc slots of the union frontier's incident CSR ranges: (uj, other,
    wa, live) — each slot's frontier vertex, the arc's other end and
    weight (one gather of the ``arc_table``), and whether it is live."""
    u, first, offs = _compact_cols(active, indptr, vcap)
    uj, eid, live = _expand_edges(u, first, offs, ecap)
    arc = arcs[eid]  # (ecap, 2)
    wa = jax.lax.bitcast_convert_type(arc[:, 1], jnp.float32)
    return uj, arc[:, 0], wa, live


def multpath_relax_csr(F: Multpath, indptr: jax.Array, arcs: jax.Array,
                       n: int, *, vcap: int, ecap: int) -> Multpath:
    """Frontier-compacted ``multpath_relax_coo`` over by-src CSR arcs.

    ``arcs`` is the by-src ``arc_table`` (dst, w). Only arcs leaving the
    union frontier are touched: active columns compact into ``vcap``
    slots, their out-arc ranges into ``ecap`` arc slots, and (nb, ecap)
    candidates scatter with the same batched 2D segment ops as the COO
    kernel. Dead arc slots carry w = inf — the COO kernel's own padding
    idiom — so they are monoid-inert. The result is exactly
    ``multpath_relax_coo`` *provided* the frontier fits — active columns
    ``<= vcap`` and incident arcs ``<= ecap`` — which the caller
    guarantees by capacity-bucket selection (``CsrAdj``): arcs from
    inactive columns hold F.w = inf in every batch row and can never win
    a segment min.
    """
    uj, dst, wa, live = _expand_arcs(jnp.isfinite(F.w), indptr, arcs,
                                     vcap, ecap)
    Fw, Fm = _gather_cols([F.w, F.m], uj)
    return _multpath_scatter(Fw, Fm, jnp.where(live, wa, INF),
                             jnp.where(live, dst, 0), n)


def centpath_relax_csr(F: Centpath, indptr_in: jax.Array,
                       arcs_in: jax.Array, n: int, *, vcap: int, ecap: int
                       ) -> Centpath:
    """Frontier-compacted ``centpath_relax_coo`` over by-dst (CSC) arcs.

    ``arcs_in`` is the by-dst ``arc_table`` (src, w). The active side of
    the Brandes action is the *child* (the arc's dst): active child
    columns compact into slots, each child's in-arc range expands, and
    (nb, ecap) candidates scatter to the predecessor side with the
    batched 2D segment ops of the COO kernel. Equals
    ``centpath_relax_coo`` under the same capacity proviso.
    """
    uj, src, wa, live = _expand_arcs(jnp.isfinite(F.w), indptr_in,
                                     arcs_in, vcap, ecap)
    alive = live & jnp.isfinite(wa)  # padding arcs never contribute
    Fw, Fp = _gather_cols([F.w, F.p], uj)
    return _centpath_scatter(Fw, Fp, wa, alive, jnp.where(alive, src, 0), n)
