"""The benchmark's graph generator and host reference, on the CPU.

The reference must agree with the engine's own oracles
(``repro.core.brandes_bc``, ``closeness_ref``, ``khop_ref``) at small
scale, while importing none of them itself.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import graph500, reference  # noqa: E402


def _graph(scale, seed):
    from repro.graphs.formats import Graph

    k = graph500.kronecker(scale, 16, 0.57, 0.19, 0.19, seed)
    return k, Graph(k.n, k.src, k.dst, np.ones(k.m, np.float32),
                    directed=False)


@pytest.mark.parametrize("scale,seed", [(8, 0), (10, 3), (9, 2**31 + 5)])
def test_kronecker_is_the_engines_rmat_graph(scale, seed):
    from repro.graphs.generators import rmat

    k = graph500.kronecker(scale, 16, 0.57, 0.19, 0.19, seed)
    g, _ = rmat(scale, 16, seed=seed).remove_isolated()
    assert (k.n, k.m) == (g.n, g.m)
    got = set(zip(k.src.tolist(), k.dst.tolist()))
    assert got == set(zip(g.src.tolist(), g.dst.tolist()))
    assert np.all(np.diff(k.src.astype(np.int64) * k.n + k.dst) > 0)


def test_generate_reads_the_configuration():
    cfg = {"scale": 8, "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
           "graph_seed": 4}
    k = graph500.generate(cfg)
    again = graph500.kronecker(8, 16, 0.57, 0.19, 0.19, 4)
    assert k.n == again.n and np.array_equal(k.src, again.src)
    assert np.array_equal(k.dst, again.dst)


@pytest.mark.parametrize("metric", ["betweenness", "closeness", "khop"])
@pytest.mark.parametrize("scale,seed", [(8, 0), (9, 5)])
def test_reference_matches_engine_oracles(metric, scale, seed):
    from repro.core import brandes_bc
    from repro.core.brandes_ref import closeness_ref, khop_ref

    k, g = _graph(scale, seed)
    a = reference.adjacency(k.n, k.src, k.dst)
    # repeats included: a sampled request may draw a source twice
    sources = np.random.default_rng(seed).integers(0, k.n, 40)
    got = reference.source_sums(a, sources, metric, hops=2)
    if metric == "betweenness":
        want = brandes_bc(g, sources=sources)
    elif metric == "closeness":
        want = closeness_ref(g, sources)
    else:
        want = khop_ref(g, sources, hops=2)
    assert reference.rel_gap(got, want) < 1e-12


def test_reference_blocks_agree(monkeypatch):
    k, _ = _graph(8, 4)
    a = reference.adjacency(k.n, k.src, k.dst)
    sources = np.arange(k.n)
    whole = reference.source_sums(a, sources)
    monkeypatch.setattr(reference, "BLOCK", 7)
    assert reference.rel_gap(reference.source_sums(a, sources), whole) < 1e-12


def test_loops_and_isolated_vertices_are_inert():
    k = graph500.kronecker(8, 16, 0.57, 0.19, 0.19, 6)
    sink = k.n + 9
    src = np.concatenate([k.src, np.full(64, sink, np.int32)])
    dst = np.concatenate([k.dst, np.full(64, sink, np.int32)])
    sources = np.arange(0, k.n, 5)
    plain = reference.source_sums(reference.adjacency(k.n, k.src, k.dst),
                                  sources)
    padded = reference.source_sums(reference.adjacency(k.n + 10, src, dst),
                                   sources)
    assert np.array_equal(padded[:k.n], plain)
    assert not padded[k.n:].any()


def test_bfloat16_rounding_departs_from_float64():
    k, _ = _graph(9, 1)
    a = reference.adjacency(k.n, k.src, k.dst)
    sources = np.arange(16)
    exact = reference.source_sums(a, sources)
    low = reference.source_sums(a, sources, rounding="bfloat16")
    assert reference.rel_gap(low, exact) > 1e-3
    with pytest.raises(ValueError):
        reference.source_sums(a, sources, rounding="int4")


def test_rel_gap_counts_non_finite_and_shape_as_wrong():
    want = np.array([0.0, 10.0, 100.0])
    assert reference.rel_gap(want, want) == 0.0
    assert reference.rel_gap(np.array([0.0, 10.0, np.nan]), want) == np.inf
    assert reference.rel_gap(want[:2], want) == np.inf
    assert reference.rel_gap(np.array([1.0, 10.0, 100.0]), want) == 1.0
