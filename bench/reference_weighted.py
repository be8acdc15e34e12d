"""Plain host reference: betweenness of a weighted graph, in Dijkstra order.

For a set of sources (repeats allowed) this computes, in float64, S1(v) =
Σ_s δ_s(v), Brandes' dependency of s on v (ordered pairs, both endpoints
excluded), on a graph with positive arc weights:

* distances d(s, ·) from ``scipy.sparse.csgraph.dijkstra``, for a block of
  sources at a time;
* the shortest-path DAG of s: the arcs u → v with d(s, u) + w = d(s, v);
* path counts σ propagated along it in increasing distance, one group of
  equal distances at a time, then dependencies δ in decreasing distance
  (Brandes), again by groups.

Equality of distances is exact for integer weights, whose sums float64
holds exactly. Self loops are dropped; of repeated arcs the lightest
counts once. Nothing here imports the engine.

``rounding`` stores every intermediate σ, δ and the result through a
narrower float (``"bfloat16"``), as ``bench.reference`` does: the control,
the reference computed in the precision below the engine's float32.
Distances stay exact, as the engine's integer-valued float32 ones are.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from bench.reference import _round, rel_gap  # noqa: F401

#: Bytes of the (block, n) float64 distances one Dijkstra call returns.
STATE_BYTES = 1 << 30


def adjacency(n: int, src: np.ndarray, dst: np.ndarray,
              w: np.ndarray) -> sp.csr_matrix:
    """Weighted adjacency A[u, v] = w(u → v), loops dropped, the lightest
    of repeated arcs kept."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float64)
    if np.any(w <= 0) or not np.all(np.isfinite(w)):
        raise ValueError("arc weights must be positive and finite")
    keep = src != dst
    src, dst, w = src[keep], dst[keep], w[keep]
    order = np.lexsort((w, dst, src))
    src, dst, w = src[order], dst[order], w[order]
    first = np.ones(src.shape[0], bool)
    first[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    return sp.csr_matrix((w[first], (src[first], dst[first])), shape=(n, n))


def _one_source(s: int, d: np.ndarray, src, dst, w, n: int,
                rounding) -> np.ndarray:
    """δ_s over every vertex, from the distances ``d`` of source s."""
    ds = d[src]
    tight = np.isfinite(ds) & (ds + w == d[dst])
    u, v = src[tight], dst[tight]
    order = np.argsort(d[v], kind="stable")
    u, v = u[order], v[order]
    # one group per distinct distance of the arcs' heads, increasing
    cuts = np.flatnonzero(np.diff(d[v])) + 1
    groups = np.split(np.arange(v.shape[0]), cuts)
    sigma = np.zeros(n)
    sigma[s] = 1.0
    for g in groups:  # every tail lies at a smaller distance: final
        heads, inv = np.unique(v[g], return_inverse=True)
        sigma[heads] = _round(np.bincount(inv, weights=sigma[u[g]]),
                              rounding)
    delta = np.zeros(n)
    for g in reversed(groups):  # every head's δ is final
        coef = _round((1.0 + delta[v[g]]) / sigma[v[g]], rounding)
        tails, inv = np.unique(u[g], return_inverse=True)
        add = np.bincount(inv, weights=coef)
        delta[tails] = _round(delta[tails] + sigma[tails] * add, rounding)
    delta[s] = 0.0
    return delta


def source_sums(a: sp.csr_matrix, sources, *,
                rounding: Optional[str] = None) -> np.ndarray:
    """S1 = Σ_s δ_s over ``sources``; (n,) float64."""
    sources = np.asarray(sources, np.int64)
    n = a.shape[0]
    coo = a.tocoo()
    src, dst = coo.row.astype(np.int64), coo.col.astype(np.int64)
    w = coo.data
    block = max(1, STATE_BYTES // (8 * max(n, 1)))
    out = np.zeros(n)
    for i in range(0, sources.shape[0], block):
        blk = sources[i:i + block]
        dist = np.atleast_2d(dijkstra(a, directed=True, indices=blk))
        for s, d in zip(blk, dist):
            out += _one_source(int(s), d, src, dst, w, n, rounding)
    return _round(out, rounding)
