"""Closed loop of exact sweeps: ``repro.bc.solve`` in exact mode, one
n_b-source batch per call, on the configuration's graph.

The planner picks the backend and n_b, as ``repro.launch.bc_run`` does.
The work is fixed by the configuration: ``batches`` chunks of n_b
sources, the head of a permutation drawn from ``graph_seed``. The run's
seed orders them (the window cycles through the order), so every seed
sweeps the same set in another order. Set-up runs the first batch of
that order once, which compiles (or loads) the one program the window
uses. The window ends at the first batch that completes after
``seconds``; ``exact_teps`` is m times the sources swept in it, over it.

``correct``: Σδ of a seeded sample of the window's batches against
``bench.reference`` on the same sources (``lam_gap``, the widest
``rel_gap``).
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from bench import graph500, reference
from bench.harness import Check


class Session:
    def __init__(self, cell, seed: int):
        from repro.bc import BCQuery, build_executor, plan
        from repro.graphs.formats import Graph

        cfg = cell.config
        self.cell, self.seed = cell, seed
        self.kg = graph500.generate(cfg)
        self.g = Graph(self.kg.n, self.kg.src, self.kg.dst,
                       np.ones(self.kg.m, np.float32), directed=False,
                       name=cfg["name"])
        self.query = BCQuery(mode="exact")
        self.plan = plan(self.g, self.query, n_devices=1)
        self.ex = build_executor(self.g, self.plan)
        self.sets = batch_sources(self.kg.n, int(cfg["graph_seed"]),
                                  self.plan.n_b,
                                  int(cell.traffic["batches"]))
        self.order = batch_order(len(self.sets), seed)
        self.done: List[Tuple[int, np.ndarray]] = []  # (batch, Σδ)
        self.elapsed = 0.0
        self.relax_calls = 0
        self.attempted = self.failed = 0
        self._sum(self.sets[self.order[0]])  # warm-up: the only shape

    def _sum(self, sources: np.ndarray) -> np.ndarray:
        from repro.bc import solve

        return solve(self.g, self.query, plan=self.plan, executor=self.ex,
                     sources=sources).lam

    def _relax_calls(self) -> int:
        occ = self.ex.occupancy_summary()
        return int(occ["relax_calls"]) if occ else 0

    def window(self, seconds: float, spans) -> None:
        calls0 = self._relax_calls()
        ends = []
        t0 = time.perf_counter()
        while not ends or ends[-1] < seconds:
            b = int(self.order[len(ends) % len(self.order)])
            with spans.span("bench.step"):
                lam = self._sum(self.sets[b])
            self.done.append((b, lam))
            ends.append(time.perf_counter() - t0)
        self.elapsed = ends[-1]
        self.relax_calls = self._relax_calls() - calls0
        self.attempted = len(self.done)
        print(f"exact_sweep: n_b={self.plan.n_b} n={self.kg.n} "
              f"m={self.kg.m} batches {[b for b, _ in self.done]} end at "
              f"{[round(t, 4) for t in ends]} s", file=sys.stderr)

    def sources_swept(self) -> int:
        return sum(self.sets[b].size for b, _ in self.done)

    def end_to_end(self) -> Dict[str, float]:
        return {"exact_teps": self.kg.m * self.sources_swept()
                / self.elapsed}

    def counters(self) -> Dict[str, float]:
        return {"window_s": self.elapsed, "batches": len(self.done),
                "relax_calls": self.relax_calls,
                "sources": self.sources_swept()}

    def release(self) -> None:
        self.ex = None
        self.g = None
        gc.collect()

    def checks(self) -> List[Check]:
        picks = check_picks(len(self.done), self.seed,
                            int(self.cell.traffic["check_batches"]))
        got = [(self.sets[self.done[j][0]], self.done[j][1]) for j in picks]
        return [Check("lam_gap", batch_gap(self.kg, got),
                      self.cell.limit("lam_gap"))]


def batch_sources(n: int, graph_seed: int, n_b: int,
                  batches: int) -> List[np.ndarray]:
    """The configuration's fixed work: ``batches`` chunks of n_b sources."""
    if n_b * batches > n:
        raise ValueError(f"{batches} batches of {n_b} sources need more "
                         f"than the graph's {n} vertices")
    perm = np.random.default_rng([graph_seed, 1]).permutation(n)
    perm = perm[:n_b * batches].astype(np.int32)
    return [perm[i * n_b:(i + 1) * n_b] for i in range(batches)]


def batch_order(batches: int, seed: int) -> np.ndarray:
    return np.random.default_rng([seed, 1]).permutation(batches)


def check_picks(n_done: int, seed: int, k: int) -> List[int]:
    """The window's batches that the reference checks, drawn from the seed."""
    rng = np.random.default_rng([seed, 2])
    return sorted(rng.choice(n_done, size=min(k, n_done),
                             replace=False).tolist())


def batch_gap(kg, got) -> float:
    """Widest ``rel_gap`` of each (sources, Σδ) in ``got`` against the
    reference; inf when there is nothing to compare."""
    if not got:
        return float("inf")
    a = reference.adjacency(kg.n, kg.src, kg.dst)
    return max(reference.rel_gap(lam, reference.source_sums(a, sources))
               for sources, lam in got)


def control_gap(cell, seed: int, n_b: int) -> float:
    """``lam_gap`` of the control: the reference computed in bfloat16, in
    the engine's place, on the batches a run with ``seed`` checks when its
    window sweeps the whole set once."""
    cfg = cell.config
    kg = graph500.generate(cfg)
    sets = batch_sources(kg.n, int(cfg["graph_seed"]), n_b,
                         int(cell.traffic["batches"]))
    order = batch_order(len(sets), seed)
    a = reference.adjacency(kg.n, kg.src, kg.dst)
    picks = check_picks(len(sets), seed, int(cell.traffic["check_batches"]))
    got = [(sets[order[j]], reference.source_sums(a, sets[order[j]],
                                                  rounding="bfloat16"))
           for j in picks]
    return batch_gap(kg, got)
