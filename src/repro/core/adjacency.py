"""Adjacency containers usable inside jit (registered pytrees).

``DenseAdj`` wraps an ``(n, n)`` float matrix with ``inf`` off-structure.
``CooAdj`` wraps padded edge arrays (static nnz). ``CsrAdj`` carries the
same arcs sorted both ways (by src and by dst) with row pointers, so its
relaxations can compact the active frontier and touch only incident arc
ranges. All expose the two monoid relaxations and the SP-DAG child count;
dispatch is static (python ``isinstance``), so a jitted function
specializes per format.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import monoids
from repro.core.monoids import Centpath, Multpath
from repro.graphs.formats import Graph, coo_to_dense, pad_edges


class RelaxStats(NamedTuple):
    """Cheap side output of one frontier-compacted relaxation.

    ``bucket`` is the capacity-ladder index that served the call
    (``len(caps)`` = the full-edge-list fallback, -1 = the backend has no
    compaction at all); ``overflow`` is 1 iff the fallback ran. ``arcs``
    is the useful work (arcs leaving the union frontier), ``slots`` the
    work done (the chosen rung's ``ecap``, or every arc on the fallback).
    ``entry_arcs`` sums, over the active (row, vertex) entries, the
    vertex's degree in the relax's direction: the arcs a relax that
    expanded entries instead of union columns would touch. It is int32,
    exact while ``n_b · E < 2**31``; past that it wraps (the count is
    then wrong, the relax is not).
    """

    nnz: jax.Array  # int32 — active frontier entries seen by this relax
    arcs: jax.Array  # int32 — arc slots the frontier's ranges needed
    bucket: jax.Array  # int32 — ladder index chosen
    overflow: jax.Array  # int32 — 1 iff the full-edge-list fallback ran
    slots: jax.Array  # int32 — arc slots the chosen branch processed
    entry_arcs: jax.Array  # int32 — Σ degree over active (row, vertex)


def no_compaction() -> RelaxStats:
    """Stats of a relax with no capacity ladder (dense, COO)."""
    zero = jnp.int32(0)
    return RelaxStats(zero, zero, jnp.int32(-1), zero, zero, zero)


def _scoped(name: str, fn):
    """``fn`` with its ops under ``jax.named_scope(name)`` — a profiler
    trace's ``tf_op`` then names the ``lax.switch`` branch that ran."""
    def run(*args):
        with jax.named_scope(name):
            return fn(*args)
    return run


def _gather_rows_scatter(src: jax.Array, dst: jax.Array, w: jax.Array,
                         n: int, sources: jax.Array) -> jax.Array:
    """Rows of the dense adjacency for ``sources``: (nb, n).

    Scatters each arc's weight into row ``searchsorted(sorted(sources),
    src)`` and reduces with one ``segment_min`` over (nb*n + 1) flat
    segments (the +1 is the dump for arcs whose src is not sampled) —
    O(E log nb + nb*n) instead of an (nb, E) boolean hit matrix. The
    final gather maps sorted rows back to the callers' order (duplicate
    sources all read the first occurrence's row).
    """
    nb = sources.shape[0]
    ss = jnp.sort(sources)
    rc = jnp.clip(jnp.searchsorted(ss, src), 0, nb - 1)
    flat = jnp.where(ss[rc] == src, rc * n + dst, nb * n)
    out = jax.ops.segment_min(w, flat, num_segments=nb * n + 1)
    out = out[:-1].reshape(nb, n)
    out = jnp.where(jnp.isfinite(out), out, jnp.inf)
    return out[jnp.searchsorted(ss, sources)]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseAdj:
    a: jax.Array  # (n, n), inf off-structure
    block: int = 512
    use_kernel: bool = False  # route dense relax through the Pallas kernels
    # Transpose hoisted out of the relax loop: computed once at build and
    # carried as a pytree leaf, so jitted relax_cp never re-transposes.
    at: Optional[jax.Array] = None

    def __post_init__(self):
        if self.at is None:
            self.at = self.a.T

    def tree_flatten(self):
        return (self.a, self.at), (self.block, self.use_kernel)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0], aux[1], children[1])

    @property
    def n(self) -> int:
        return self.a.shape[-1]

    def gather_rows(self, sources: jax.Array) -> jax.Array:
        return self.a[sources, :]

    @jax.named_scope("relax.dense")
    def relax_mp(self, F: Multpath) -> Multpath:
        if self.use_kernel:
            from repro.kernels import ops as kops

            w, m = kops.multpath_matmul(F.w, F.m, self.a)
            return Multpath(w, m)
        return monoids.multpath_relax_dense(F, self.a, block=self.block)

    @jax.named_scope("relax.dense")
    def relax_cp(self, F: Centpath) -> Centpath:
        if self.use_kernel:
            from repro.kernels import ops as kops

            w, p, c = kops.centpath_matmul(F.w, F.p, self.at)
            return Centpath(w, p, c)
        return monoids.centpath_relax_dense(F, self.at, block=self.block)

    def count_sp_children(self, Tw: jax.Array) -> jax.Array:
        return monoids.count_sp_children_dense(Tw, self.a, block=self.block)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CooAdj:
    src: jax.Array  # (E,) int32, padded
    dst: jax.Array  # (E,) int32
    w: jax.Array  # (E,) float32, padding = inf
    n_static: int

    def tree_flatten(self):
        return (self.src, self.dst, self.w), (self.n_static,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux[0])

    @property
    def n(self) -> int:
        return self.n_static

    def gather_rows(self, sources: jax.Array) -> jax.Array:
        return _gather_rows_scatter(self.src, self.dst, self.w, self.n,
                                    sources)

    @jax.named_scope("relax.coo")
    def relax_mp(self, F: Multpath) -> Multpath:
        return monoids.multpath_relax_coo(F, self.src, self.dst, self.w, self.n)

    @jax.named_scope("relax.coo")
    def relax_cp(self, F: Centpath) -> Centpath:
        return monoids.centpath_relax_coo(F, self.src, self.dst, self.w, self.n)

    def count_sp_children(self, Tw: jax.Array) -> jax.Array:
        return monoids.count_sp_children_coo(Tw, self.src, self.dst, self.w,
                                             self.n)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CsrAdj:
    """Dual-sorted arc lists with frontier-compacted relaxations.

    The same arcs are carried twice: sorted by src with row pointers
    (``indptr``/``src``/``dst``/``w``) and sorted by dst (``indptr_in``,
    ``dst_in`` — the CSC side MFBr's backward action expands). What a
    compacted relax reads at an arc id, the arc's other end and its
    weight, sits in one ``monoids.arc_table`` per side (``arcs``,
    ``arcs_in``), built once here so that each relax reads it with one
    gather. The full-edge fallback runs each relax over the arcs sorted
    by its segment id, so that every full-arc scatter is told its ids
    are sorted: MFBF (segments by dst) over the by-dst arcs, MFBr and the
    SP-child count (by src) over the by-src arrays. ``check_sorted``
    holds the two orders when the container is built.
    ``caps`` is the static power-of-two capacity ladder ``((vcap, ecap),
    ...)``: each relax counts the *union-column* frontier (vertices
    active in any batch row) and its incident arcs, picks the smallest
    bucket that fits with ``lax.switch``, and falls back to the
    full-edge-list COO relax when every bucket overflows — so results
    never depend on the ladder, only the work does.
    """

    indptr: jax.Array  # (n+1,) int32 row pointers into the by-src arrays
    src: jax.Array  # (E,) int32, sorted ascending
    dst: jax.Array  # (E,) int32
    w: jax.Array  # (E,) float32, padding = inf
    arcs: jax.Array  # (E, 2) int32 by-src arc_table: dst, w
    indptr_in: jax.Array  # (n+1,) int32 row pointers into ``arcs_in``
    arcs_in: jax.Array  # (E, 2) int32 by-dst arc_table: src, w
    dst_in: jax.Array  # (E,) int32 by-dst arc heads, sorted ascending
    n_static: int
    caps: Tuple[Tuple[int, int], ...]

    def tree_flatten(self):
        return ((self.indptr, self.src, self.dst, self.w, self.arcs,
                 self.indptr_in, self.arcs_in, self.dst_in),
                (self.n_static, self.caps))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0], aux[1])

    @property
    def n(self) -> int:
        return self.n_static

    def check_sorted(self) -> None:
        """Raise unless ``src`` and ``dst_in`` are non-decreasing. The
        full-edge relaxes and ``count_sp_children`` tell XLA that these
        segment ids are sorted, and a false claim gives wrong answers
        without an error."""
        for name in ("src", "dst_in"):
            ids = np.asarray(getattr(self, name))
            if np.any(ids[1:] < ids[:-1]):
                raise ValueError(f"CsrAdj.{name} is not sorted ascending")

    def gather_rows(self, sources: jax.Array) -> jax.Array:
        return _gather_rows_scatter(self.src, self.dst, self.w, self.n,
                                    sources)

    @jax.named_scope("relax.pick")
    def _pick_bucket(self, Fw: jax.Array, indptr: jax.Array):
        """Count the union-column frontier (entries where ``Fw`` is
        finite) and choose the smallest fitting bucket. ``nnz`` is active
        *columns* (vertices live in any batch row — what the compacting
        relaxes expand), ``arcs`` their incident arc total, ``slots`` the
        arc slots the chosen branch processes, ``entry_arcs`` the arcs of
        every active (row, vertex) entry (from the same per-column count
        that marks the union columns)."""
        deg = indptr[1:] - indptr[:-1]
        per_col = jnp.sum(jnp.isfinite(Fw), axis=0, dtype=jnp.int32)
        colmask = per_col > 0
        nnz = jnp.sum(colmask.astype(jnp.int32))
        arcs = jnp.sum(jnp.where(colmask, deg, 0)).astype(jnp.int32)
        entry_arcs = jnp.sum(per_col * deg, dtype=jnp.int32)
        bucket = jnp.int32(len(self.caps))
        for i in reversed(range(len(self.caps))):
            vcap, ecap = self.caps[i]
            fits = (nnz <= vcap) & (arcs <= ecap)
            bucket = jnp.where(fits, jnp.int32(i), bucket)
        slots = jnp.asarray([e for _, e in self.caps] + [self.src.shape[0]],
                            jnp.int32)[bucket]
        return nnz, arcs, bucket, slots, entry_arcs

    def _switch(self, F, indptr, rung, full_edge):
        """Run ``F`` through the smallest fitting rung (``rung(vcap,
        ecap)``) or, past the ladder, ``full_edge``; with its stats."""
        nnz, arcs, bucket, slots, entry_arcs = self._pick_bucket(F.w, indptr)
        branches = [_scoped(f"relax.rung{i}", rung(v, e))
                    for i, (v, e) in enumerate(self.caps)]
        branches.append(_scoped("relax.full_edge", full_edge))
        out = jax.lax.switch(bucket, branches, F)
        overflow = (bucket == len(self.caps)).astype(jnp.int32)
        return out, RelaxStats(nnz, arcs, bucket, overflow, slots,
                               entry_arcs)

    def _full_edge_mp(self, F: Multpath) -> Multpath:
        """MFBF over every arc in by-dst order: its segment ids
        (``dst_in``) are sorted. Within a segment the arcs keep the
        by-src order, so the sums add what the by-src relax adds, in the
        same order."""
        src_in = self.arcs_in[:, 0]
        w_in = jax.lax.bitcast_convert_type(self.arcs_in[:, 1], jnp.float32)
        return monoids.multpath_relax_coo(F, src_in, self.dst_in, w_in,
                                          self.n, sorted_seg=True)

    def relax_mp_stats(self, F: Multpath) -> Tuple[Multpath, RelaxStats]:
        return self._switch(
            F, self.indptr,
            lambda v, e: functools.partial(
                monoids.multpath_relax_csr, indptr=self.indptr,
                arcs=self.arcs, n=self.n, vcap=v, ecap=e),
            self._full_edge_mp)

    def relax_cp_stats(self, F: Centpath) -> Tuple[Centpath, RelaxStats]:
        return self._switch(
            F, self.indptr_in,
            lambda v, e: functools.partial(
                monoids.centpath_relax_csr, indptr_in=self.indptr_in,
                arcs_in=self.arcs_in, n=self.n, vcap=v, ecap=e),
            lambda Fb: monoids.centpath_relax_coo(
                Fb, self.src, self.dst, self.w, self.n, sorted_seg=True))

    def relax_mp(self, F: Multpath) -> Multpath:
        return self.relax_mp_stats(F)[0]

    def relax_cp(self, F: Centpath) -> Centpath:
        return self.relax_cp_stats(F)[0]

    def count_sp_children(self, Tw: jax.Array) -> jax.Array:
        return monoids.count_sp_children_coo(Tw, self.src, self.dst, self.w,
                                             self.n, sorted_seg=True)


def frontier_caps(n_b: int, n: int, m: int) -> Tuple[Tuple[int, int], ...]:
    """Power-of-two ``(vcap, ecap)`` escalation ladder for compaction.

    ``vcap`` bounds the compacted union-frontier *columns* (vertices
    active in any batch row), ``ecap`` their incident arc slots. A
    compact relax costs ``n_b * ecap`` candidate work plus an O(n)
    compaction, against ``n_b * m`` for the full COO fallback — so the
    ladder's ecaps climb power-of-two from ~m/32 and stop short of
    ``m``, letting the fallback absorb saturated frontiers (typically
    the 1–3 mid-sweep iterations) while the compact buckets win the
    launch and drain phases. ``vcap = n`` on every rung: column count
    never overflows, only arc volume escalates.
    """
    full_e = max(m, 1)
    caps = []
    e = 2
    while e < max(full_e // 32, 2):
        e *= 2
    while e < full_e and len(caps) < 4:
        caps.append((int(n), int(e)))
        e *= 4
    if not caps:
        caps.append((int(n), int(full_e)))
    return tuple(caps)


def dense_adj_from_graph(g: Graph, *, block: int = 512,
                         use_kernel: bool = False) -> DenseAdj:
    return DenseAdj(jnp.asarray(coo_to_dense(g)), block=block,
                    use_kernel=use_kernel)


def coo_adj_from_graph(g: Graph, *, pad_multiple: int = 128) -> CooAdj:
    src, dst, w = pad_edges(g, multiple=pad_multiple)
    return CooAdj(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w), g.n)


def csr_adj_from_graph(g: Graph, *, n_b: int = 64,
                       caps: Optional[Tuple[Tuple[int, int], ...]] = None,
                       pad_multiple: int = 1) -> CsrAdj:
    """Build the dual-sorted container on the host (stable sorts).

    The by-dst order is the by-src order stably sorted by dst, so within
    each dst the arcs keep their by-src order.
    ``n_b`` sizes the default capacity ladder (it bounds the batch axis
    of the frontiers the relaxes will see); pass explicit ``caps`` to
    override — tests force escalation with caps like ``((1, 1),)``.
    """
    src, dst, w = pad_edges(g, multiple=pad_multiple)
    order = np.argsort(src, kind="stable")
    src_s, dst_s, w_s = src[order], dst[order], w[order]
    indptr = np.zeros(g.n + 1, np.int32)
    np.add.at(indptr, src_s + 1, 1)
    np.cumsum(indptr, out=indptr)
    order_in = np.argsort(dst_s, kind="stable")
    src_in, dst_in, w_in = src_s[order_in], dst_s[order_in], w_s[order_in]
    indptr_in = np.zeros(g.n + 1, np.int32)
    np.add.at(indptr_in, dst_in + 1, 1)
    np.cumsum(indptr_in, out=indptr_in)
    if caps is None:
        caps = frontier_caps(n_b, g.n, int(src_s.shape[0]))
    host = CsrAdj(indptr, src_s, dst_s, w_s, monoids.arc_table(dst_s, w_s),
                  indptr_in, monoids.arc_table(src_in, w_in), dst_in, g.n,
                  tuple(caps))
    host.check_sorted()
    return jax.tree.map(jnp.asarray, host)
