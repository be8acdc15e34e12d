"""Closed loop of exact sweeps on a weighted graph: ``exact_sweep`` with
one integer weight per undirected edge.

The configuration's ``weights`` (``kind`` ``uniform_int``, ``low``,
``high``, ``weight_seed``) give every undirected edge of the Graph500
graph a weight drawn uniformly from [low, high], in the generator's edge
order (edges sorted by their lower, then higher endpoint), and both of
its arcs carry it. Everything else is ``exact_sweep``'s: the planner's
backend and n_b, the same fixed batches of sources
(``batch_sources``), the run seed's order (``batch_order``), the
warm-up, the window rule, ``exact_teps`` = m × sources swept over the
window.

``correct``: Σδ of a seeded sample of the window's batches
(``check_picks``) against ``bench.reference_weighted``, the Dijkstra-order
float64 reference (``lam_gap``).

``counters()`` adds the window's deltas of the executor's entry counters
(``entry_arcs``, ``entries_bf``, ``reached_bf``) and of ``arc_slots``,
and the batch width ``n_b``. A program without those counters leaves
them out, and the metrics that read them report nothing.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import graph500, reference_weighted
from bench.harness import Check
from bench.traffic import exact_sweep
from bench.traffic.exact_sweep import batch_order, batch_sources, check_picks

#: Occupancy counters whose window deltas ``counters()`` reports.
OCC_KEYS = ("entry_arcs", "entries_bf", "reached_bf", "arc_slots")


def arc_weights(kg, spec: Dict) -> np.ndarray:
    """(m,) float32 weight of each arc of ``kg``: one uniform integer in
    [low, high] per undirected edge, drawn from ``weight_seed`` in edge
    order, the same on both arcs."""
    if spec.get("kind") != "uniform_int":
        raise ValueError(f"unknown weights kind {spec.get('kind')!r}")
    lo = np.minimum(kg.src, kg.dst).astype(np.int64)
    hi = np.maximum(kg.src, kg.dst).astype(np.int64)
    key = lo * kg.n + hi
    edges = key[kg.src < kg.dst]  # sorted: the arcs are sorted by (src, dst)
    rng = np.random.default_rng(int(spec["weight_seed"]))
    w = rng.integers(int(spec["low"]), int(spec["high"]) + 1, edges.shape[0])
    return w[np.searchsorted(edges, key)].astype(np.float32)


class Session(exact_sweep.Session):
    def __init__(self, cell, seed: int):
        from repro.bc import BCQuery, build_executor, plan
        from repro.graphs.formats import Graph

        cfg = cell.config
        self.cell, self.seed = cell, seed
        self.kg = graph500.generate(cfg)
        self.w = arc_weights(self.kg, cfg["weights"])
        self.g = Graph(self.kg.n, self.kg.src, self.kg.dst, self.w,
                       directed=False, name=cfg["name"])
        self.query = BCQuery(mode="exact")
        self.plan = plan(self.g, self.query, n_devices=1)
        self.ex = build_executor(self.g, self.plan)
        self.sets = batch_sources(self.kg.n, int(cfg["graph_seed"]),
                                  self.plan.n_b,
                                  int(cell.traffic["batches"]))
        self.order = batch_order(len(self.sets), seed)
        self.done = []  # (batch, Σδ)
        self.elapsed = 0.0
        self.relax_calls = 0
        self.occ_deltas: Dict[str, int] = {}
        self.attempted = self.failed = 0
        self._sum(self.sets[self.order[0]])  # warm-up: the only shape

    def _occupancy(self) -> Dict:
        return self.ex.occupancy_summary() or {}

    def window(self, seconds: float, spans) -> None:
        occ0 = self._occupancy()
        super().window(seconds, spans)
        occ1 = self._occupancy()
        self.occ_deltas = {k: occ1[k] - occ0.get(k, 0) for k in OCC_KEYS
                           if k in occ1}

    def counters(self) -> Dict[str, float]:
        return dict(super().counters(), n_b=self.plan.n_b,
                    **self.occ_deltas)

    def checks(self) -> List[Check]:
        picks = check_picks(len(self.done), self.seed,
                            int(self.cell.traffic["check_batches"]))
        got = [(self.sets[self.done[j][0]], self.done[j][1]) for j in picks]
        return [Check("lam_gap", batch_gap(self.kg, self.w, got),
                      self.cell.limit("lam_gap"))]


def batch_gap(kg, w, got) -> float:
    """Widest ``rel_gap`` of each (sources, Σδ) in ``got`` against the
    weighted reference; inf when there is nothing to compare."""
    if not got:
        return float("inf")
    a = reference_weighted.adjacency(kg.n, kg.src, kg.dst, w)
    return max(reference_weighted.rel_gap(
        lam, reference_weighted.source_sums(a, sources))
        for sources, lam in got)


def control_gap(cell, seed: int, n_b: int) -> float:
    """``lam_gap`` of the control: the weighted reference computed in
    bfloat16, in the engine's place, on the batches a run with ``seed``
    checks when its window sweeps the whole set once."""
    cfg = cell.config
    kg = graph500.generate(cfg)
    w = arc_weights(kg, cfg["weights"])
    sets = batch_sources(kg.n, int(cfg["graph_seed"]), n_b,
                         int(cell.traffic["batches"]))
    order = batch_order(len(sets), seed)
    a = reference_weighted.adjacency(kg.n, kg.src, kg.dst, w)
    picks = check_picks(len(sets), seed, int(cell.traffic["check_batches"]))
    got = [(sets[order[j]], reference_weighted.source_sums(
        a, sets[order[j]], rounding="bfloat16")) for j in picks]
    return batch_gap(kg, w, got)
