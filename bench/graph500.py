"""Graph500 Kronecker graphs, made from a configuration's seed.

The edge recipe is the Graph500 generator's ("Graph Generation":
SCALE, edgefactor 16, initiator A/B/C/D = 0.57/0.19/0.19/0.05): each of
``edgefactor * 2**scale`` edges picks one quadrant per level. Then, as
the paper preprocesses its inputs, self loops and duplicate edges are
dropped, every edge is stored as both arcs, and isolated vertices are
removed (ids compacted in increasing order). No vertex-label scrambling.

A configuration fixes the graph's seed (``graph_seed``), so every run of
a cell sweeps the same graph and compiles the same shapes.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Kronecker:
    """The real graph: ``n`` vertices, ``m`` arcs sorted by (src, dst)."""

    n: int
    src: np.ndarray  # (m,) int32
    dst: np.ndarray  # (m,) int32

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


def kronecker(scale: int, edgefactor: int, a: float, b: float, c: float,
              seed: int) -> Kronecker:
    """Symmetrized, deduplicated Kronecker graph without isolated vertices."""
    n0 = 1 << scale
    nnz = n0 * edgefactor
    rng = np.random.default_rng(seed)
    src = np.zeros(nnz, np.int64)
    dst = np.zeros(nnz, np.int64)
    for _ in range(scale):
        r = rng.random(nnz)
        down = r >= a + b
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src = src * 2 + down
        dst = dst * 2 + right
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    edges = np.unique(lo * n0 + hi)
    lo, hi = edges // n0, edges % n0
    used = np.zeros(n0, bool)
    used[lo] = True
    used[hi] = True
    remap = np.cumsum(used) - 1
    lo, hi = remap[lo], remap[hi]
    s = np.concatenate([lo, hi])
    d = np.concatenate([hi, lo])
    n = int(used.sum())
    order = np.argsort(s * n + d, kind="stable")
    return Kronecker(n, s[order].astype(np.int32), d[order].astype(np.int32))


def generate(cfg: dict) -> Kronecker:
    """The configuration's graph."""
    return kronecker(int(cfg["scale"]), int(cfg["edgefactor"]),
                     float(cfg["a"]), float(cfg["b"]), float(cfg["c"]),
                     int(cfg["graph_seed"]))
