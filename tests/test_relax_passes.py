"""The relax layer's indexed passes: how many, and what they compute.

Each distinct index vector of a relax gets one gather (fields read at the
same index are stacked into one table) and each reducer one scatter (the
centpath p and c sums share a window). ``test_relax_index_passes`` pins
the count of arc-sized gathers and scatters in the lowered program of each
relax branch and of the SP-child count, and which scatters are told that
their segment ids are sorted (``CsrAdj``'s full-edge fallback and SP-child
count, and no other); the bitwise tests hold the stacked relaxes to the
per-field formulations kept below as references.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import monoids
from repro.core.adjacency import csr_adj_from_graph
from repro.core.monoids import INF, Centpath, Multpath
from repro.graphs.formats import Graph, pad_edges

# ---------------------------------------------------------------------------
# Per-field reference formulations: one gather per field read, one scatter
# per field reduced.
# ---------------------------------------------------------------------------


def ref_multpath_relax_coo(F, src, dst, w, n):
    cand = F.w[:, src] + w[None, :]
    minw = jax.ops.segment_min(cand.T, dst, num_segments=n).T
    tie = (cand == minw[:, dst]) & jnp.isfinite(cand)
    contrib = jnp.where(tie, F.m[:, src], 0.0)
    m = jax.ops.segment_sum(contrib.T, dst, num_segments=n).T
    minw = jnp.where(m > 0, minw, INF)
    return Multpath(minw, m)


def ref_centpath_relax_coo(F, src, dst, w, n):
    cand = F.w[:, dst] - w[None, :]
    active = jnp.isfinite(F.w[:, dst]) & jnp.isfinite(w)[None, :]
    cand = jnp.where(active, cand, -INF)
    maxw = jax.ops.segment_max(cand.T, src, num_segments=n).T
    tie = (cand == maxw[:, src]) & jnp.isfinite(cand)
    p = jax.ops.segment_sum(jnp.where(tie, F.p[:, dst], 0.0).T, src,
                            num_segments=n).T
    c = jax.ops.segment_sum(jnp.where(tie, 1.0, 0.0).T, src,
                            num_segments=n).T
    maxw = jnp.where(c > 0, maxw, -INF)
    return Centpath(maxw, p, c)


def _ref_compact_cols(mask, indptr, vcap):
    n = mask.shape[1]
    cols = jnp.nonzero(jnp.any(mask, axis=0), size=vcap, fill_value=n)[0]
    valid = cols < n
    u = jnp.where(valid, cols, 0).astype(jnp.int32)
    deg = jnp.where(valid, indptr[u + 1] - indptr[u], 0)
    return u, jnp.cumsum(deg)


def _ref_expand_edges(u, offs, indptr, ecap):
    vcap = u.shape[0]
    pos = jnp.arange(ecap, dtype=offs.dtype)
    starts = jnp.concatenate([jnp.zeros((1,), offs.dtype), offs[:-1]])
    slots = jnp.arange(vcap, dtype=jnp.int32)
    tgt = jnp.where(offs > starts, starts, ecap)
    owner = jnp.zeros((ecap,), jnp.int32).at[tgt].max(slots, mode="drop")
    j = jax.lax.cummax(owner)
    live = pos < offs[-1]
    eid = jnp.where(live, indptr[u[j]] + (pos - starts[j]), 0)
    return j, eid.astype(jnp.int32), live


def ref_multpath_relax_csr(F, indptr, dst, w, n, *, vcap, ecap):
    u, offs = _ref_compact_cols(jnp.isfinite(F.w), indptr, vcap)
    j, eid, live = _ref_expand_edges(u, offs, indptr, ecap)
    uj = u[j]
    wa = jnp.where(live, w[eid], INF)
    seg = jnp.where(live, dst[eid], 0)
    cand = F.w[:, uj] + wa[None, :]
    minw = jax.ops.segment_min(cand.T, seg, num_segments=n).T
    tie = (cand == minw[:, seg]) & jnp.isfinite(cand)
    m = jax.ops.segment_sum(jnp.where(tie, F.m[:, uj], 0.0).T, seg,
                            num_segments=n).T
    minw = jnp.where(m > 0, minw, INF)
    return Multpath(minw, m)


def ref_centpath_relax_csr(F, indptr_in, src_in, w_in, n, *, vcap, ecap):
    u, offs = _ref_compact_cols(jnp.isfinite(F.w), indptr_in, vcap)
    j, eid, live = _ref_expand_edges(u, offs, indptr_in, ecap)
    uj = u[j]
    wa = w_in[eid]
    alive = live & jnp.isfinite(wa)
    seg = jnp.where(alive, src_in[eid], 0)
    Fw = F.w[:, uj]
    cand = jnp.where(alive[None, :] & jnp.isfinite(Fw),
                     Fw - wa[None, :], -INF)
    maxw = jax.ops.segment_max(cand.T, seg, num_segments=n).T
    tie = (cand == maxw[:, seg]) & jnp.isfinite(cand)
    p = jax.ops.segment_sum(jnp.where(tie, F.p[:, uj], 0.0).T, seg,
                            num_segments=n).T
    c = jax.ops.segment_sum(jnp.where(tie, 1.0, 0.0).T, seg,
                            num_segments=n).T
    maxw = jnp.where(c > 0, maxw, -INF)
    return Centpath(maxw, p, c)


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------


def _graph(seed, n, nnz, max_w):
    """Random directed graph with small integer weights (many exact ties)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, nnz)
    dst = rng.integers(0, n, nnz)
    w = rng.integers(1, max_w + 1, nnz).astype(np.float32)
    return Graph(n, src, dst, w).dedup()


def _frontiers(seed, nb, n, density):
    """(Multpath, Centpath) with integer weights (exact ties), a fully
    inactive last row, and MFBr's c = 1 on every active entry."""
    rng = np.random.default_rng(seed + 1)
    act = rng.random((nb, n)) < density
    act[-1] = False  # an empty frontier row
    w = rng.integers(0, 4, (nb, n)).astype(np.float32)
    mp = Multpath(jnp.asarray(np.where(act, w, np.inf).astype(np.float32)),
                  jnp.asarray(np.where(act, rng.integers(1, 5, (nb, n)), 0)
                              .astype(np.float32)))
    cp = Centpath(jnp.asarray(np.where(act, w, -np.inf).astype(np.float32)),
                  jnp.asarray(np.where(act, rng.random((nb, n)), 0)
                              .astype(np.float32)),
                  jnp.asarray(act.astype(np.float32)))
    return mp, cp


def _by_dst(adj):
    """The by-dst predecessor and weight arrays of a ``CsrAdj``."""
    t = np.asarray(adj.arcs_in)
    return jnp.asarray(t[:, 0]), jnp.asarray(t[:, 1].view(np.float32))


def _assert_bitwise(got, ref):
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Pass counts in the lowered program
# ---------------------------------------------------------------------------

N, NB, VCAP, ECAP = 37, 3, 23, 96


def _indexed_ops(fn, *args):
    """[(op name, index count, indices_are_sorted)] of every gather and
    scatter ``fn`` lowers to (StableHLO, before any compiler pass)."""
    mod = jax.jit(fn).lower(*args).compiler_ir("stablehlo")
    found = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    name = o.operation.name
                    if name in ("stablehlo.gather", "stablehlo.scatter"):
                        shape = list(o.operands[1].type.shape)
                        attr = ("dimension_numbers" if name.endswith("gather")
                                else "scatter_dimension_numbers")
                        dims = str(o.operation.attributes[attr])
                        ivd = int(re.search(r"index_vector_dim = (\d+)",
                                            dims).group(1))
                        if ivd < len(shape):
                            shape.pop(ivd)
                        srt = o.operation.attributes["indices_are_sorted"]
                        found.append((name.split(".")[1],
                                      int(np.prod(shape, dtype=np.int64)),
                                      bool(srt.value)))
                    walk(o.operation)

    walk(mod.operation)
    return found


def _count(found, kind, size):
    return sum(1 for k, c, _ in found if k == kind and c == size)


def _sorted_flags(found, kind, size):
    return {s for k, c, s in found if k == kind and c == size}


@pytest.fixture(scope="module")
def small_csr():
    g = _graph(11, N, 160, 3)
    adj = csr_adj_from_graph(g, caps=((VCAP, ECAP),))
    E = int(adj.src.shape[0])
    # the index counts below tell the passes apart only if these differ
    assert len({E, N, NB, 2 * NB, VCAP, ECAP, N + 1}) == 7, E
    return adj, E


@pytest.mark.parametrize("branch,gathers,scatters", [
    ("multpath_coo", 2, 2),
    ("centpath_coo", 2, 2),
    ("multpath_rung", 4, 2),
    ("centpath_rung", 4, 2),
    ("multpath_full_edge", 2, 2),
    ("centpath_full_edge", 2, 2),
    ("csr_count_sp_children", 2, 1),
    ("coo_count_sp_children", 2, 1),
])
def test_relax_index_passes(small_csr, branch, gathers, scatters):
    """One arc-sized gather per index vector, one scatter per reducer:
    COO — the stacked frontier gather and the winner gather, the
    min/max scatter and the (stacked) sum scatter; a rung adds the
    per-slot table gather and the arc table gather, and keeps its one
    slot-sized owner scatter; the SP-child count gathers at both ends
    and scatters once. Only ``CsrAdj``'s full-edge branches (read from
    its whole ladder, next to its rung) and its SP-child count tell XLA
    that their arc-sized scatters' ids are sorted; the plain COO
    helpers and the rungs do not."""
    adj, E = small_csr
    mp, cp = _frontiers(0, NB, N, 0.4)
    coo = (adj.src, adj.dst, adj.w, N)
    if branch == "multpath_coo":
        found = _indexed_ops(lambda F: monoids.multpath_relax_coo(F, *coo),
                             mp)
    elif branch == "centpath_coo":
        found = _indexed_ops(lambda F: monoids.centpath_relax_coo(F, *coo),
                             cp)
    elif branch == "multpath_rung":
        found = _indexed_ops(lambda F: monoids.multpath_relax_csr(
            F, adj.indptr, adj.arcs, N, vcap=VCAP, ecap=ECAP), mp)
    elif branch == "centpath_rung":
        found = _indexed_ops(lambda F: monoids.centpath_relax_csr(
            F, adj.indptr_in, adj.arcs_in, N, vcap=VCAP, ecap=ECAP), cp)
    elif branch == "multpath_full_edge":
        found = _indexed_ops(lambda F: adj.relax_mp_stats(F)[0], mp)
    elif branch == "centpath_full_edge":
        found = _indexed_ops(lambda F: adj.relax_cp_stats(F)[0], cp)
    elif branch == "csr_count_sp_children":
        found = _indexed_ops(adj.count_sp_children, mp.w)
    else:
        found = _indexed_ops(lambda T: monoids.count_sp_children_coo(T, *coo),
                             mp.w)
    size = ECAP if branch.endswith("rung") else E
    assert _count(found, "gather", size) == gathers, found
    assert _count(found, "scatter", size) == scatters, found
    told_sorted = branch.endswith("full_edge") or branch.startswith("csr")
    assert _sorted_flags(found, "scatter", size) == {told_sorted}, found
    if branch.endswith("rung") or branch.endswith("full_edge"):
        # the rung (alone, or beside the fallback in the ladder): its
        # arc-slot scatters and its owner scatter are not sorted
        assert _count(found, "scatter", VCAP) == 1, found  # the owners
        assert _sorted_flags(found, "scatter", VCAP) == {False}, found
    if branch.endswith("full_edge"):
        assert _count(found, "scatter", ECAP) == 2, found
        assert _sorted_flags(found, "scatter", ECAP) == {False}, found


# ---------------------------------------------------------------------------
# Bitwise equality with the per-field formulations
# ---------------------------------------------------------------------------

CASES = [  # seed, n, arcs drawn, max weight, frontier density, pad multiple
    (0, 9, 30, 1, 0.3, 1),
    (1, 17, 70, 2, 0.5, 32),
    (2, 24, 120, 3, 0.2, 1),
    (3, 31, 200, 1, 0.6, 64),
    (4, 12, 20, 2, 0.05, 32),
    (5, 40, 260, 4, 0.35, 128),
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"seed{c[0]}")
def test_coo_relax_bitwise_matches_per_field(case):
    """Stacked COO relaxes == per-field ones, bitwise, on padded arc
    lists (padding arcs carry w = inf), with exact ties and an empty
    frontier row."""
    seed, n, nnz, max_w, density, pad = case
    adj = csr_adj_from_graph(_graph(seed, n, nnz, max_w), n_b=4,
                             pad_multiple=pad)
    mp, cp = _frontiers(seed, 4, n, density)
    args = (adj.src, adj.dst, adj.w, n)
    _assert_bitwise(monoids.multpath_relax_coo(mp, *args),
                    ref_multpath_relax_coo(mp, *args))
    _assert_bitwise(monoids.centpath_relax_coo(cp, *args),
                    ref_centpath_relax_coo(cp, *args))


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"seed{c[0]}")
def test_csr_relax_bitwise_matches_per_field(case):
    """Stacked CSR rungs == per-field rungs == per-field COO, bitwise,
    at every rung of a ladder the frontier fits (vcap, ecap large
    enough) — padding arcs, exact ties and an empty frontier row
    included."""
    seed, n, nnz, max_w, density, pad = case
    adj = csr_adj_from_graph(_graph(seed, n, nnz, max_w), n_b=4,
                             pad_multiple=pad)
    E = int(adj.src.shape[0])
    mp, cp = _frontiers(seed, 4, n, density)
    src_in, w_in = _by_dst(adj)
    coo = (adj.src, adj.dst, adj.w, n)
    for vcap, ecap in ((n, E), (n + 5, E + 7)):
        caps = dict(vcap=vcap, ecap=ecap)
        got = monoids.multpath_relax_csr(mp, adj.indptr, adj.arcs, n, **caps)
        _assert_bitwise(got, ref_multpath_relax_csr(
            mp, adj.indptr, adj.dst, adj.w, n, **caps))
        _assert_bitwise(got, ref_multpath_relax_coo(mp, *coo))
        got = monoids.centpath_relax_csr(cp, adj.indptr_in, adj.arcs_in, n,
                                         **caps)
        _assert_bitwise(got, ref_centpath_relax_csr(
            cp, adj.indptr_in, src_in, w_in, n, **caps))
        _assert_bitwise(got, ref_centpath_relax_coo(cp, *coo))


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"seed{c[0]}")
def test_csr_ladder_bitwise_matches_per_field(case):
    """Through ``CsrAdj``'s ladder — its own caps, and ``((1, 1),)``,
    which every non-empty frontier overflows into the full-edge fallback
    — each relax equals the per-field COO relax, bitwise."""
    seed, n, nnz, max_w, density, pad = case
    g = _graph(seed, n, nnz, max_w)
    mp, cp = _frontiers(seed, 4, n, density)
    for adj in (csr_adj_from_graph(g, n_b=4, pad_multiple=pad),
                csr_adj_from_graph(g, caps=((1, 1),), pad_multiple=pad)):
        coo = (adj.src, adj.dst, adj.w, n)
        got, st_mp = jax.jit(adj.relax_mp_stats)(mp)
        _assert_bitwise(got, ref_multpath_relax_coo(mp, *coo))
        got, st_cp = jax.jit(adj.relax_cp_stats)(cp)
        _assert_bitwise(got, ref_centpath_relax_coo(cp, *coo))
        if adj.caps == ((1, 1),):
            assert int(st_mp.overflow) == int(st_cp.overflow) == 1


def test_arc_tables_hold_the_sorted_arcs():
    """``arcs``/``arcs_in`` are the by-src [dst, w] and by-dst [src, w]
    arcs, weights as their float32 bit patterns (inf padding kept)."""
    g = _graph(7, 15, 60, 3)
    adj = csr_adj_from_graph(g, n_b=4, pad_multiple=32)
    arcs = np.asarray(adj.arcs)
    assert arcs.dtype == np.int32 and arcs.shape == (adj.src.shape[0], 2)
    np.testing.assert_array_equal(arcs[:, 0], np.asarray(adj.dst))
    np.testing.assert_array_equal(arcs[:, 1].view(np.float32),
                                  np.asarray(adj.w))
    src_in, w_in = _by_dst(adj)
    order = np.argsort(np.asarray(adj.dst), kind="stable")
    np.testing.assert_array_equal(np.asarray(src_in),
                                  np.asarray(adj.src)[order])
    np.testing.assert_array_equal(np.asarray(w_in), np.asarray(adj.w)[order])
    assert np.isinf(np.asarray(w_in)).any()


def _shuffled(g, seed):
    """``g``'s arcs in a random order: not the canonical (src, dst) one."""
    perm = np.random.default_rng(seed).permutation(g.nnz)
    return Graph(g.n, g.src[perm], g.dst[perm], g.w[perm])


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"seed{c[0]}")
def test_full_edge_sorted_bitwise_matches_by_src(case):
    """The forced fallback (``caps=((1, 1),)``) runs MFBF over the by-dst
    arcs and MFBr over the by-src arrays, both scattering sorted ids; each
    equals the per-field COO relax over the by-src arrays bitwise. The
    multiplicities span 2**±12 and p is non-integer, so the tie sums round
    and the check sees the order in which each segment adds; on the canonical
    graph and on its arcs shuffled."""
    seed, n, nnz, max_w, density, pad = case
    g = _graph(seed, n, nnz, max_w)
    mp, cp = _frontiers(seed, 4, n, density)
    frac = 2.0 ** np.random.default_rng(seed + 2).uniform(-12, 12,
                                                           mp.m.shape)
    mp = Multpath(mp.w, jnp.where(mp.m > 0, mp.m * frac, 0.0)
                  .astype(jnp.float32))
    for graph in (g, _shuffled(g, seed)):
        adj = csr_adj_from_graph(graph, caps=((1, 1),), pad_multiple=pad)
        coo = (adj.src, adj.dst, adj.w, n)
        got, st = jax.jit(adj.relax_mp_stats)(mp)
        assert int(st.overflow) == 1
        _assert_bitwise(got, ref_multpath_relax_coo(mp, *coo))
        got, st = jax.jit(adj.relax_cp_stats)(cp)
        assert int(st.overflow) == 1
        _assert_bitwise(got, ref_centpath_relax_coo(cp, *coo))


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"seed{c[0]}")
def test_csr_count_sp_children_matches_unsorted_coo(case):
    """``CsrAdj.count_sp_children`` (by-src arcs, sorted ids) equals the
    plain COO count over the same padded arcs in a shuffled order, on the
    shortest-path distances from 4 sources (so that children exist)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    seed, n, nnz, max_w, density, pad = case
    g = _graph(seed, n, nnz, max_w)
    adj = csr_adj_from_graph(g, n_b=4, pad_multiple=pad)
    Tw = jnp.asarray(dijkstra(csr_matrix((g.w, (g.src, g.dst)), (n, n)),
                              indices=np.arange(4)).astype(np.float32))
    src, dst, w = pad_edges(g, multiple=pad)
    perm = np.random.default_rng(seed).permutation(src.shape[0])
    ref = monoids.count_sp_children_coo(
        Tw, jnp.asarray(src[perm]), jnp.asarray(dst[perm]),
        jnp.asarray(w[perm]), n)
    got = jax.jit(adj.count_sp_children)(Tw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert int(np.asarray(ref).sum()) > 0


def test_csr_adj_check_sorted_rejects_unsorted_ids():
    """``csr_adj_from_graph`` checks that the ids its full-edge scatters
    claim sorted are sorted; a hand-built container whose ``dst_in`` (or
    ``src``) is out of order is refused."""
    g = _graph(3, 31, 200, 1)
    adj = csr_adj_from_graph(_shuffled(g, 3), n_b=4, pad_multiple=64)
    adj.check_sorted()
    for name in ("dst_in", "src"):
        ids = np.asarray(getattr(adj, name))
        bad = dataclasses.replace(adj, **{name: jnp.asarray(ids[::-1])})
        with pytest.raises(ValueError, match=f"CsrAdj.{name} is not sorted"):
            bad.check_sorted()
