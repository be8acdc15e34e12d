"""The weighted cell's graph, host reference and traffic, on the CPU.

``bench.reference_weighted`` must agree with the engine's own Brandes
oracle (``repro.core.brandes_bc``, Dijkstra on weighted graphs) while
importing none of it; its bfloat16 control must fail the cell's limit
and the engine's CSR sweep must pass it.
"""
import copy
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import graph500, harness, reference_weighted  # noqa: E402
from bench.traffic import exact_sweep_weighted as esw  # noqa: E402

SPEC = harness.load_json(harness.SPEC_FILE)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
WEIGHTS = {"kind": "uniform_int", "low": 1, "high": 100, "weight_seed": 1}
LIMIT = harness.resolve(SPEC, "exact-g500w-s18").limit("lam_gap")


def _weighted(scale, seed, weights=WEIGHTS):
    from repro.graphs.formats import Graph

    k = graph500.kronecker(scale, 16, 0.57, 0.19, 0.19, seed)
    w = esw.arc_weights(k, weights)
    return k, w, Graph(k.n, k.src, k.dst, w, directed=False)


def test_arc_weights_are_per_edge_integers_from_the_seed():
    k, w, _ = _weighted(9, 2)
    assert w.dtype == np.float32 and w.shape == (k.m,)
    assert w.min() >= 1 and w.max() <= 100 and np.all(w == np.round(w))
    both = dict(zip(zip(k.src.tolist(), k.dst.tolist()), w.tolist()))
    assert all(both[(v, u)] == x for (u, v), x in both.items())
    assert np.array_equal(esw.arc_weights(k, WEIGHTS), w)
    other = esw.arc_weights(k, dict(WEIGHTS, weight_seed=2))
    assert not np.array_equal(other, w)
    # many distinct weights, and equal ones: ties stay possible
    assert 90 <= np.unique(w).size <= 100
    with pytest.raises(ValueError):
        esw.arc_weights(k, dict(WEIGHTS, kind="uniform_float"))


@pytest.mark.parametrize("scale,seed", [(8, 0), (9, 5), (10, 2**31 + 3)])
def test_reference_matches_the_engines_brandes(scale, seed):
    from repro.core import brandes_bc

    k, w, g = _weighted(scale, seed)
    a = reference_weighted.adjacency(k.n, k.src, k.dst, w)
    # repeats included: a sampled request may draw a source twice
    sources = np.random.default_rng(seed).integers(0, k.n, 24)
    got = reference_weighted.source_sums(a, sources)
    want = brandes_bc(g, sources=sources)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-9)
    assert reference_weighted.rel_gap(got, want) < 1e-12


def test_reference_blocks_loops_and_repeated_arcs_are_inert(monkeypatch):
    k, w, _ = _weighted(8, 4)
    sources = np.arange(0, k.n, 3)
    whole = reference_weighted.source_sums(
        reference_weighted.adjacency(k.n, k.src, k.dst, w), sources)
    # a loop and a heavier copy of every arc change no shortest path
    src = np.concatenate([k.src, k.src, [0]])
    dst = np.concatenate([k.dst, k.dst, [0]])
    ww = np.concatenate([w, w + 1, [1.0]])
    a = reference_weighted.adjacency(k.n, src, dst, ww)
    monkeypatch.setattr(reference_weighted, "STATE_BYTES", 8 * k.n * 5)
    assert reference_weighted.rel_gap(
        reference_weighted.source_sums(a, sources), whole) < 1e-12
    with pytest.raises(ValueError):
        reference_weighted.adjacency(k.n, k.src, k.dst, w * 0)


def _solve_csr(g, sources, caps):
    """Σδ over ``sources`` from ``repro.bc.solve`` on a ``CsrAdj`` whose
    ladder is ``caps``; with the executor's occupancy summary."""
    from repro.bc import BCQuery, ExecutionConfig, build_executor, plan, solve
    from repro.core.adjacency import csr_adj_from_graph

    q = BCQuery(mode="exact", n_b=16,
                execution=ExecutionConfig(backend="csr"))
    pl = plan(g, q, n_devices=1)
    ex = build_executor(g, pl)
    ex._adj = csr_adj_from_graph(g, n_b=pl.n_b, caps=caps)
    lam = solve(g, q, plan=pl, executor=ex, sources=sources).lam
    return lam, ex.occupancy_summary()


def test_engine_passes_the_limit_and_the_bfloat16_control_fails_it():
    k, w, g = _weighted(9, 7)
    a = reference_weighted.adjacency(k.n, k.src, k.dst, w)
    sources = np.random.default_rng(3).choice(k.n, 16, replace=False)
    want = reference_weighted.source_sums(a, sources)
    control = reference_weighted.source_sums(a, sources,
                                             rounding="bfloat16")
    assert reference_weighted.rel_gap(control, want) > LIMIT
    # small caps: the sweeps escalate through both rungs and overflow
    lam, occ = _solve_csr(g, sources, ((64, 256), (k.n, 2048)))
    rungs = {r[1] for r in occ["rows_bf"] + occ["rows_br"]}
    assert rungs == {0, 1, 2}
    assert reference_weighted.rel_gap(lam, want) <= LIMIT
    # weights make MFBF re-enter entries; MFBr retires each once
    assert occ["entries_bf"] > occ["reached_bf"]
    assert occ["entries_br"] == occ["reached_br"]


def _small_cell(**traffic):
    """The weighted cell at SCALE 9 (the planner's n_b is 64 there),
    resolved from the real files."""
    cell = harness.resolve(SPEC, "exact-g500w-s18")
    cell.config = dict(cell.config, scale=9)
    cell.workload = copy.deepcopy(cell.workload)
    cell.workload["traffic"].update(traffic)
    return cell


def test_weighted_cell_runs_through_the_harness():
    cell = _small_cell(batches=3)
    t0 = time.monotonic()
    traced = harness.run_cell(cell, 2**31 + 11, 0.5, True, t0, CPU)
    assert traced["correct"], traced["checks"]
    assert traced["checks"]["lam_gap"]["value"] <= LIMIT
    c = traced["counters"]
    assert c["n_b"] == 64 and c["batches"] == traced["attempted"] >= 1
    assert c["entries_bf"] >= c["reached_bf"] > 0
    assert 0 < c["entry_arcs"] <= c["n_b"] * c["arc_slots"]
    m = traced["metrics"]
    assert m["frontier_reentry.weighted"]["value"] == pytest.approx(
        c["entries_bf"] / c["reached_bf"])
    assert m["frontier_reentry.weighted"]["value"] > 1.0
    assert 0 < m["relax_entry_arc_pct.weighted"]["value"] <= 100
    assert m["relax_calls_per_batch"]["value"] == pytest.approx(
        c["relax_calls"] / c["batches"])
    plain = harness.run_cell(cell, 5, 0.5, False, t0, CPU)
    assert plain["correct"]
    assert set(plain["metrics"]) == {"exact_teps", "setup_s"}
    assert plain["metrics"]["exact_teps"]["value"] > 0


def test_the_cells_bfloat16_control_fails_the_limit():
    """The control as the cell computes it: the weighted reference in
    bfloat16 on the batches a seed's window checks."""
    gaps = [esw.control_gap(_small_cell(batches=3), seed, n_b=64)
            for seed in (1, 2**31 + 7)]
    assert min(gaps) > LIMIT


def test_a_program_without_the_entry_counters_reports_no_entry_metrics(
        monkeypatch):
    """On a program whose occupancy lacks the entry counters the cell
    still runs; its two new metrics are left out, not failed."""
    from repro.bc.executor import SingleHostExecutor

    real = SingleHostExecutor.occupancy_summary

    def older(self):
        occ = real(self)
        if occ:
            for key in ("entry_arcs", "entries_bf", "entries_br",
                        "reached_bf", "reached_br"):
                occ.pop(key)
        return occ

    monkeypatch.setattr(SingleHostExecutor, "occupancy_summary", older)
    out = harness.run_cell(_small_cell(batches=2), 9, 0.2, True,
                           time.monotonic(), CPU)
    assert out["correct"]
    assert "entry_arcs" not in out["counters"]
    assert "arc_slots" in out["counters"]
    assert "frontier_reentry.weighted" not in out["metrics"]
    assert "relax_entry_arc_pct.weighted" not in out["metrics"]
    assert "relax_calls_per_batch" in out["metrics"]


def test_the_exact_cell_reports_the_metrics_it_reported_before():
    cell = harness.resolve(SPEC, "exact-g500-s18")
    assert [m["name"] for m in cell.end_to_end] == ["exact_teps", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle_pct.exact", "step_device_ms", "relax_calls_per_batch"]
    new = harness.resolve(SPEC, "exact-g500w-s18")
    assert [m["name"] for m in new.end_to_end] == ["exact_teps", "setup_s"]
    assert [m["name"] for m in new.per_layer] == [
        "step_device_ms", "relax_calls_per_batch",
        "frontier_reentry.weighted", "relax_entry_arc_pct.weighted"]


def test_weighted_config_is_the_exact_configs_graph_with_weights():
    base = harness.load_json(ROOT + "/bench/configs/g500-s18.json")
    cfg = harness.resolve(SPEC, "exact-g500w-s18").config
    for key in ("generator", "scale", "edgefactor", "a", "b", "c",
                "graph_seed", "chips"):
        assert cfg[key] == base[key], key
    assert cfg["weights"] == WEIGHTS
