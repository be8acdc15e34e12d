"""Plain host reference: per-source statistics of an unweighted graph.

For a set of sources (repeats allowed) this computes, in float64,

* ``betweenness``: S1(v) = Σ_s δ_s(v), Brandes' dependency of s on v
  (ordered pairs, both endpoints excluded);
* ``closeness``: S1(v) = Σ_s d(s, v) over finite distances, v ≠ s;
* ``khop``: S1(v) = |{s : 1 ≤ d(s, v) ≤ hops}|.

All sources run together, level-synchronously: one sparse product per
BFS level forward (path counts σ) and one per level backward
(dependencies δ). Self loops are dropped; they lie on no shortest path.
Nothing here imports the engine.

``rounding`` stores every intermediate σ, δ and the result through a
narrower float (``"bfloat16"``): that is the control, the reference
computed in the precision below the engine's float32.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.sparse as sp

METRICS = ("betweenness", "closeness", "khop")

#: Sources swept together; bounds the (n, block) float64 state.
BLOCK = 256


def adjacency(n: int, src: np.ndarray, dst: np.ndarray) -> sp.csr_matrix:
    """0/1 adjacency A[u, v] = 1 for each arc u → v, loops dropped."""
    keep = src != dst
    a = sp.csr_matrix((np.ones(int(keep.sum())), (src[keep], dst[keep])),
                      shape=(n, n))
    a.data[:] = 1.0  # repeated arcs count once
    return a


def _round(x: np.ndarray, rounding: Optional[str]) -> np.ndarray:
    if rounding is None:
        return x
    if rounding != "bfloat16":
        raise ValueError(f"unknown rounding {rounding!r}")
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def _levels(at: sp.csr_matrix, sources: np.ndarray, rounding):
    """BFS levels (-1 unreached) and path counts, both (n, b)."""
    n, b = at.shape[0], sources.shape[0]
    cols = np.arange(b)
    level = np.full((n, b), -1, np.int32)
    sigma = np.zeros((n, b))
    level[sources, cols] = 0
    sigma[sources, cols] = 1.0
    frontier = sigma.copy()
    d = 0
    while True:
        nxt = _round(at @ frontier, rounding)
        new = (nxt > 0) & (level < 0)
        if not new.any():
            return level, sigma, d
        d += 1
        level[new] = d
        sigma[new] = nxt[new]
        frontier = np.where(new, nxt, 0.0)


def _betweenness(a, level, sigma, depth, sources, rounding) -> np.ndarray:
    delta = np.zeros_like(sigma)
    for d in range(depth, 0, -1):
        at_d = level == d
        coef = np.zeros_like(sigma)
        coef[at_d] = _round((1.0 + delta[at_d]) / sigma[at_d], rounding)
        contrib = _round(a @ coef, rounding)
        up = level == d - 1
        delta[up] = _round(sigma[up] * contrib[up], rounding)
    delta[sources, np.arange(sources.shape[0])] = 0.0
    return delta.sum(axis=1)


def source_sums(a: sp.csr_matrix, sources, metric: str = "betweenness", *,
                hops: int = 0, rounding: Optional[str] = None) -> np.ndarray:
    """S1 over ``sources`` for ``metric``; (n,) float64."""
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}")
    if metric == "khop" and hops < 1:
        raise ValueError("khop needs hops >= 1")
    sources = np.asarray(sources, np.int64)
    at = a.T.tocsr()
    out = np.zeros(a.shape[0])
    for i in range(0, sources.shape[0], BLOCK):
        blk = sources[i:i + BLOCK]
        level, sigma, depth = _levels(at, blk, rounding)
        if metric == "betweenness":
            out += _betweenness(a, level, sigma, depth, blk, rounding)
        elif metric == "closeness":
            out += np.where(level > 0, level, 0).sum(axis=1)
        else:
            out += ((level >= 1) & (level <= hops)).sum(axis=1)
    return _round(out, rounding)


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest gap max |got - want| / (|want| + 1).

    Relative where a value counts one pair's dependency or more, absolute
    below: a vertex on few shortest paths has a λ near 0, where a
    relative gap measures nothing but rounding.
    """
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / (np.abs(want) + 1.0)))
