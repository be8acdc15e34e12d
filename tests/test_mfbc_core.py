"""Correctness of the core MFBC algorithms vs the numpy Brandes oracle.

Covers paper Lemma 4.1 (MFBF distances + multiplicities), Lemma 4.2 (MFBr
partial centrality factors), and Theorem 4.3 (full λ), on directed and
undirected, weighted and unweighted graphs, through the dense, COO and
frontier-CSR relaxation backends.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _graphs import GRAPHS
from repro.core import (brandes_bc, bfs_bc, coo_adj_from_graph,
                        dense_adj_from_graph, mfbc, mfbf)
from repro.core.adjacency import csr_adj_from_graph
from repro.core.mfbc import mfbc_batch_moments_traced
from repro.core.monoids import INF, Centpath, Multpath
from repro.graphs.generators import path_graph, ring_of_cliques


def _adj(g, backend):
    if backend == "dense":
        return dense_adj_from_graph(g)
    if backend == "csr":
        return csr_adj_from_graph(g, n_b=16)
    return coo_adj_from_graph(g)


@pytest.mark.parametrize("backend", ["dense", "coo", "csr"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_mfbf_matches_dijkstra(gname, backend):
    """Lemma 4.1: T(s, v) = (τ(s, v), σ̄(s, v))."""
    g = GRAPHS[gname]()
    sources = np.arange(min(g.n, 16), dtype=np.int32)
    _, dist_ref, sigma_ref = brandes_bc(g, sources=sources, return_aux=True)
    adj = _adj(g, backend)
    Tw, Tm = jax.jit(lambda a, s: mfbf(a, s))(adj, jnp.asarray(sources))
    Tw, Tm = np.asarray(Tw).copy(), np.asarray(Tm).copy()
    # The (s, s) entry differs by convention: the oracle says dist 0, MFBF
    # computes the shortest closed walk (masked to inf inside mfbc_batch
    # before MFBr — betweenness excludes t = s). Skip the diagonal.
    rows = np.arange(len(sources))
    for arr in (Tw, dist_ref):
        arr[rows, sources] = np.inf
    for arr in (Tm, sigma_ref):
        arr[rows, sources] = 0.0
    np.testing.assert_allclose(Tw, dist_ref, rtol=0, atol=0)
    np.testing.assert_allclose(Tm, sigma_ref, rtol=1e-6)


@pytest.mark.parametrize("backend", ["dense", "coo", "csr"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_mfbc_matches_brandes(gname, backend):
    """Theorem 4.3: λ(v) = Σ_{s,t} σ(s,t,v)/σ̄(s,t)."""
    g = GRAPHS[gname]()
    lam_ref = brandes_bc(g)
    lam = mfbc(g, n_b=8, backend=backend)
    np.testing.assert_allclose(lam, lam_ref, rtol=1e-5, atol=1e-8)


def _columns_with_arcs(indptr, lo, hi):
    """A vertex set whose arcs (by ``indptr``) number in ``(lo, hi]``."""
    deg = np.diff(np.asarray(indptr))
    cols, total = np.zeros(deg.shape[0], bool), 0
    for v in np.argsort(-deg, kind="stable"):
        if total > lo:
            break
        if total + deg[v] <= hi:
            cols[v], total = True, total + deg[v]
    assert lo < total <= hi, (lo, total, hi)
    return jnp.asarray(cols)


@pytest.mark.parametrize("gname", ["er40_w", "roc4x4", "star33"])
def test_csr_default_ladder_mfbr_falls_back_where_mfbf_has_a_rung(gname):
    """Through the default ladder MFBr keeps only the rungs cheaper than
    its full-edge fallback. A frontier whose union arcs fit MFBF's top
    rung but not MFBr's runs MFBr's fallback (``bucket = len(caps)``,
    ``overflow = 1``, every arc a slot) and MFBF's rung; the sweeps take
    such fallbacks, and λ still matches Brandes."""
    g = GRAPHS[gname]()  # undirected: in-arcs and out-arcs agree
    adj = csr_adj_from_graph(g, n_b=16)
    caps, E = adj.caps, int(adj.src.shape[0])
    assert 0 < adj.cp_rungs < len(caps)
    cols = _columns_with_arcs(adj.indptr_in, caps[adj.cp_rungs - 1][1],
                              caps[-1][1])
    on = cols.astype(jnp.float32)[None, :]
    _, st = jax.jit(adj.relax_cp_stats)(
        Centpath(jnp.where(cols, 0.0, -INF)[None, :], on, on))
    assert (int(st.bucket), int(st.overflow), int(st.slots)) == (
        len(caps), 1, E)
    _, st = jax.jit(adj.relax_mp_stats)(
        Multpath(jnp.where(cols, 0.0, INF)[None, :], on))
    assert adj.cp_rungs <= int(st.bucket) < len(caps)
    assert int(st.overflow) == 0

    src = jnp.arange(16, dtype=jnp.int32)
    *_, tr_br = mfbc_batch_moments_traced(adj, src, jnp.ones(16, bool))
    k = int(tr_br.iters)
    rows = list(zip(np.asarray(tr_br.bucket)[:k], np.asarray(tr_br.arcs)[:k]))
    assert any(b == len(caps) and a <= caps[-1][1] for b, a in rows), rows
    assert int(tr_br.overflows) == sum(b == len(caps) for b, _ in rows)
    np.testing.assert_allclose(mfbc(g, n_b=16, backend="csr"), brandes_bc(g),
                               rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("gname", ["path8", "roc4x4", "er40", "rmat5", "uni60"])
def test_bfs_baseline_matches_brandes(gname):
    """The CombBLAS-like BFS baseline agrees on unweighted graphs."""
    g = GRAPHS[gname]()
    lam_ref = brandes_bc(g)
    lam = bfs_bc(g, n_b=8, max_depth=g.n)
    np.testing.assert_allclose(lam, lam_ref, rtol=1e-5, atol=1e-8)


def test_mfbc_fori_iterate_matches_while():
    g = GRAPHS["er40_w"]()
    lam_w = mfbc(g, n_b=8, iterate="while")
    lam_f = mfbc(g, n_b=8, iterate="fori", max_iters=g.n)
    np.testing.assert_allclose(lam_w, lam_f, rtol=1e-6)


def test_mfbc_batch_sizes_equivalent():
    """n_b is a performance knob only (paper: time/storage tradeoff)."""
    g = GRAPHS["er40"]()
    lam1 = mfbc(g, n_b=5)
    lam2 = mfbc(g, n_b=40)
    np.testing.assert_allclose(lam1, lam2, rtol=1e-6)


def test_path_graph_analytic():
    """On a path 0-1-...-7, interior vertex k has λ = 2·k·(n-1-k)."""
    n = 8
    g = path_graph(n)
    lam = mfbc(g, n_b=4)
    expect = np.array([2.0 * k * (n - 1 - k) for k in range(n)])
    np.testing.assert_allclose(lam, expect, rtol=1e-6)


def test_weighted_changes_centrality():
    """Weights must actually matter (the paper's weighted contribution)."""
    g_u = ring_of_cliques(3, 4)
    g_w = ring_of_cliques(3, 4, weighted=True, seed=2)
    lam_u = mfbc(g_u, n_b=6)
    lam_w = mfbc(g_w, n_b=6)
    assert not np.allclose(lam_u, lam_w)
    np.testing.assert_allclose(lam_w, brandes_bc(g_w), rtol=1e-5, atol=1e-8)


def test_disconnected_graph():
    """Unreachable pairs contribute nothing (and nothing NaNs out)."""
    import repro.graphs.formats as F
    src = np.array([0, 1, 3, 4], np.int32)
    dst = np.array([1, 0, 4, 3], np.int32)
    w = np.ones(4, np.float32)
    g = F.Graph(6, src, dst, w, directed=False)
    lam = mfbc(g, n_b=3)
    lam_ref = brandes_bc(g)
    assert np.all(np.isfinite(lam))
    np.testing.assert_allclose(lam, lam_ref, atol=1e-8)


def test_source_subset_approximation():
    g = GRAPHS["er40"]()
    srcs = np.array([0, 3, 7, 21], np.int32)
    lam = mfbc(g, n_b=4, sources=srcs)
    lam_ref = brandes_bc(g, sources=srcs)
    np.testing.assert_allclose(lam, lam_ref, rtol=1e-5, atol=1e-8)
