"""Metric-generic solver substrate tests.

One upload, one scheduler, many graph analytics: closeness, k-hop
reachability and connected components ride the same planned, fused,
QoS-scheduled serving path as betweenness. Three layers of evidence:

* **parity** — every metric's exact sweep, through every registered
  backend (dense / COO / CSR adjacency), matches a plain-numpy
  reference (BFS/Dijkstra closeness, hop-limited BFS, union-find);
* **fusion** — cross-metric fused ``step_segmented`` ticks (betweenness
  and closeness rows sharing one collective) are *bitwise* equal to
  running each slot's rows alone, and a mixed-metric service run
  retires each request bit-identical to serving it by itself;
* **facade stability** — the default metric prices, plans and
  serializes exactly as before (no ``metric``/``hops`` keys in default
  plan JSON), while forward-only metrics are priced at one sweep
  against betweenness's two.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic sweep, see tests/_hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, strategies as st

from _graphs import GRAPHS
from repro.bc import (BatchAssembler, BCQuery, ExecutionConfig, build_executor,
                      fuse_group, metric_spec, plan, registered_metrics,
                      scatter, solve)
from repro.core import cc_ref, closeness_ref, khop_ref
from repro.graphs.generators import rmat
from repro.serve.bc_service import BCRequest, BCService

_CACHE = {}

BACKENDS = ("dense", "coo", "csr")


def _graph():
    if "g" not in _CACHE:
        g = rmat(6, 8, seed=5)
        g, _ = g.remove_isolated()
        _CACHE["g"] = g
    return _CACHE["g"]


def _host_executor():
    if "host" not in _CACHE:
        g = _graph()
        _CACHE["host"] = build_executor(
            g, plan(g, BCQuery(mode="approx", n_b=64), n_devices=1))
    return _CACHE["host"]


# ------------------------------------------------------------- registry
def test_registry_and_fuse_groups():
    names = registered_metrics()
    assert {"betweenness", "closeness", "khop", "components"} <= set(names)
    bc = metric_spec("betweenness")
    assert bc.sweeps == 2 and bc.needs_backward and bc.sampled
    cl = metric_spec("closeness")
    assert cl.sweeps == 1 and not cl.needs_backward and cl.sampled
    kh = metric_spec("khop")
    assert kh.bounded and kh.sampled
    cc = metric_spec("components")
    assert cc.fixed_point and not cc.sampled
    with pytest.raises(ValueError, match="registered"):
        metric_spec("nope")
    # fusion compatibility: metrics sharing the unbounded forward sweep
    # share one group; hop bounds and fixed points do not
    assert fuse_group("betweenness") == fuse_group("closeness")
    assert fuse_group("khop", 2) == fuse_group("khop", 2)
    assert fuse_group("khop", 2) != fuse_group("khop", 3)
    assert fuse_group("khop", 2) != fuse_group("betweenness")
    assert fuse_group("components") != fuse_group("closeness")


def test_query_and_plan_metric_validation():
    with pytest.raises(ValueError, match="hops"):
        BCQuery(metric="khop")  # bounded metric needs a bound
    with pytest.raises(ValueError, match="hops"):
        BCQuery(metric="closeness", hops=3)  # unbounded takes none
    with pytest.raises(ValueError, match="fixed point"):
        BCQuery(mode="approx", metric="components")  # exact only


def test_default_plan_json_has_no_metric_keys():
    """Wire stability: a default-metric plan serializes byte-for-byte as
    before the metric field existed; non-default metrics record
    themselves."""
    g = _graph()
    d = plan(g, BCQuery(mode="approx"), n_devices=1).to_json()
    assert "metric" not in d and "hops" not in d
    d = plan(g, BCQuery(mode="approx", metric="closeness"),
             n_devices=1).to_json()
    assert d["metric"] == "closeness" and "hops" not in d
    d = plan(g, BCQuery(mode="approx", metric="khop", hops=3),
             n_devices=1).to_json()
    assert d["metric"] == "khop" and d["hops"] == 3


def test_forward_only_metrics_price_one_sweep():
    """The planner prices closeness (forward sweep only) at half the
    iteration volume of betweenness (forward + backward) for the same
    configuration, and records it in the plan."""
    g = _graph()
    pb = plan(g, BCQuery(mode="approx", n_b=32), n_devices=1)
    pc = plan(g, BCQuery(mode="approx", n_b=32, metric="closeness"),
              n_devices=1)
    # comm volume scales with spec.sweeps × est_iters × n_batches: the
    # forward-only metric pays exactly half the sweep volume
    assert pc.predicted_comm_bytes * 2 == pb.predicted_comm_bytes
    assert pc.predicted_seconds < pb.predicted_seconds


# ------------------------------------------------- parity vs references
@st.composite
def _rmat_cases(draw):
    scale = draw(st.integers(min_value=3, max_value=5))
    degree = draw(st.integers(min_value=2, max_value=8))
    seed = draw(st.integers(min_value=0, max_value=2 ** 31 - 1))
    metric = draw(st.sampled_from(
        ["closeness", "khop:1", "khop:2", "khop:3", "components"]))
    return scale, degree, seed, metric


@settings(max_examples=10, deadline=None)
@given(_rmat_cases())
def test_metric_parity_on_random_rmat_all_backends(case):
    """Every metric's exact sweep == its plain-numpy reference, through
    the dense, COO and frontier-CSR adjacency backends alike — the
    generic masked-(Tw, Tm) pipeline is backend-agnostic by
    construction, this pins it."""
    scale, degree, seed, metric = case
    g = rmat(scale, degree, seed=seed)
    name, _, hops = metric.partition(":")
    if name == "closeness":
        ref, exact = closeness_ref(g), False
    elif name == "khop":
        ref, exact = khop_ref(g, hops=int(hops or 0)), True
    else:
        ref, exact = cc_ref(g), True
    for backend in BACKENDS:
        q = BCQuery(mode="exact", metric=name, hops=int(hops or 0),
                    execution=ExecutionConfig(backend=backend))
        lam = solve(g, q, plan=plan(g, q, n_devices=1)).lam
        if exact:  # integer-valued counts/labels: exact in f32/f64
            np.testing.assert_array_equal(lam, ref, err_msg=backend)
        else:
            np.testing.assert_allclose(lam, ref, rtol=1e-4, atol=1e-5,
                                       err_msg=backend)


@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("metric", ["closeness", "khop:2", "components"])
def test_metrics_match_oracles_on_fixed_graphs(metric, gname):
    """The parity above on the fixed oracle graphs: directed, weighted,
    a 79-hop path past the trace cap and a star, on every backend."""
    g = GRAPHS[gname]()
    name, _, hops = metric.partition(":")
    for backend in BACKENDS:
        q = BCQuery(mode="exact", metric=name, hops=int(hops or 0),
                    execution=ExecutionConfig(backend=backend))
        lam = solve(g, q, plan=plan(g, q, n_devices=1)).lam
        if name == "closeness":
            np.testing.assert_allclose(lam, closeness_ref(g), rtol=1e-4,
                                       atol=1e-5, err_msg=backend)
        elif name == "khop":
            np.testing.assert_array_equal(lam, khop_ref(g, hops=int(hops)),
                                          err_msg=backend)
        else:
            np.testing.assert_array_equal(lam, cc_ref(g), err_msg=backend)


def test_components_labels_bitwise_union_find():
    """CC labels are the min vertex id per component — bitwise equal to
    union-find, on every backend."""
    g = _graph()
    ref = cc_ref(g)
    for backend in BACKENDS:
        q = BCQuery(mode="exact", metric="components",
                    execution=ExecutionConfig(backend=backend))
        res = solve(g, q, plan=plan(g, q, n_devices=1))
        np.testing.assert_array_equal(res.lam, ref, err_msg=backend)
        assert res.converged and res.n_swept == g.n


def test_approx_closeness_converges_to_reference():
    """Closeness through the adaptive sampling driver: the estimator's
    n-scaled mean converges onto the exact farness profile."""
    g = _graph()
    res = solve(g, BCQuery(mode="approx", metric="closeness", eps=0.02,
                           delta=0.1, seed=7))
    assert res.approx is not None and res.converged
    ref = closeness_ref(g)
    # λ̂ estimates Σ_s d(s, v); top of the farness order must agree
    assert set(res.topk(3)) <= set(np.argsort(ref)[::-1][:8])


# --------------------------------------------------- cross-metric fusion
def test_single_metric_segmented_matches_legacy_dispatch():
    """``metrics=('betweenness', ...)`` (all default) must route through
    the exact same compiled step as the legacy no-metrics call —
    bitwise, not just close."""
    ex = _host_executor()
    n = _graph().n
    rng = np.random.default_rng(3)
    src = rng.integers(0, n, 24).astype(np.int32)
    sid = np.sort(rng.integers(0, 3, 24).astype(np.int32))
    valid = np.ones(24, bool)
    legacy = ex.step_segmented(src, valid, sid, 3)
    tagged = ex.step_segmented(src, valid, sid, 3,
                               metrics=("betweenness",) * 3)
    for a, b in zip(legacy, tagged):
        np.testing.assert_array_equal(a, b)


@settings(max_examples=8, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["betweenness", "closeness"]),
                          st.integers(min_value=1, max_value=40)),
                min_size=1, max_size=5),
       st.integers(min_value=0, max_value=2 ** 16))
def test_cross_metric_fused_bitwise_equals_sequential(slots, seed):
    """The tentpole guarantee: a fused tick mixing betweenness and
    closeness rows in one ``step_segmented`` collective returns, for
    every slot, statistics bitwise-identical to running that slot's
    rows alone under its own metric."""
    ex = _host_executor()
    n = _graph().n
    rng = np.random.default_rng(seed)
    demand = [(j, rng.integers(0, n, ln).astype(np.int32))
              for j, (_, ln) in enumerate(slots)]
    metric_of = {j: m for j, (m, _) in enumerate(slots)}
    for fb in BatchAssembler(ex).assemble(demand):
        metrics = tuple(metric_of[key] for key in fb.slots)
        s1, s2, nr = ex.step_segmented(fb.sources, fb.valid, fb.slot_ids,
                                       fb.n_slots, metrics=metrics)
        for j, key in enumerate(fb.slots):
            rows = fb.sources[(fb.slot_ids == j) & fb.valid]
            b1, b2, bn = ex.step_segmented(
                rows, np.ones(rows.shape[0], bool),
                np.zeros(rows.shape[0], np.int32), 1,
                metrics=(metric_of[key],))
            np.testing.assert_array_equal(s1[j], b1[0])
            np.testing.assert_array_equal(s2[j], b2[0])
            np.testing.assert_array_equal(nr[j], bn[0])


def test_khop_fused_group_bitwise():
    """Hop-bounded slots fuse with matching bounds: two khop(2) slots
    share one bounded sweep, bitwise equal to solo runs."""
    ex = _host_executor()
    n = _graph().n
    rng = np.random.default_rng(11)
    demand = [(0, rng.integers(0, n, 9).astype(np.int32)),
              (1, rng.integers(0, n, 13).astype(np.int32))]
    for fb in BatchAssembler(ex).assemble(demand):
        s1, s2, nr = ex.step_segmented(fb.sources, fb.valid, fb.slot_ids,
                                       fb.n_slots,
                                       metrics=("khop",) * fb.n_slots,
                                       hops=2)
        for j, key in enumerate(fb.slots):
            rows = fb.sources[(fb.slot_ids == j) & fb.valid]
            b1, _, _ = ex.step_segmented(
                rows, np.ones(rows.shape[0], bool),
                np.zeros(rows.shape[0], np.int32), 1,
                metrics=("khop",), hops=2)
            np.testing.assert_array_equal(s1[j], b1[0])


# ------------------------------------------------------- service parity
def _serve(reqs, **kw):
    svc = BCService({"web": _graph()}, n_slots=4, **kw)
    for r in reqs:
        svc.submit(r)
    out = {r.rid: r for r in svc.run()}
    assert not svc.exhausted
    return out


def test_service_mixed_metrics_equal_isolated_runs():
    """A mixed-metric service run (betweenness + closeness fused into
    shared ticks, khop in its own group) retires every request with the
    same answer as a service run holding only that request — same
    (seed, rid) stream, same epoch schedule, same statistics.

    Closeness and khop compare *bitwise*: alone or fused they run the
    same metric-generic compiled step, and the segment sums accumulate
    each slot's rows in the same order. Betweenness alone dispatches the
    legacy byte-stable step (the pre-metric compiled program), while
    fused next to closeness it runs the generic one — two XLA programs
    whose f32 reduction orders may differ by an ulp, so it compares to
    float tolerance (the tick-level bitwise guarantee is
    ``test_cross_metric_fused_bitwise_equals_sequential``)."""
    reqs = [
        BCRequest(rid=0, graph="web", eps=0.1, delta=0.1, seed=3),
        BCRequest(rid=1, graph="web", eps=0.1, delta=0.1, seed=3,
                  metric="closeness"),
        BCRequest(rid=2, graph="web", eps=0.1, delta=0.1, seed=3,
                  metric="khop", hops=2),
    ]
    together = _serve(reqs)
    assert len(together) == 3
    for req in reqs:
        alone = _serve([req])[req.rid]
        mixed = together[req.rid]
        assert mixed.n_samples == alone.n_samples
        assert mixed.n_epochs == alone.n_epochs
        assert mixed.converged == alone.converged
        if req.metric == "betweenness":
            assert mixed.topk == alone.topk
            np.testing.assert_allclose(mixed.lam, alone.lam, rtol=1e-5)
            np.testing.assert_allclose(mixed.halfwidth, alone.halfwidth,
                                       rtol=1e-4, atol=1e-9)
        else:
            assert mixed.topk == alone.topk
            np.testing.assert_array_equal(mixed.lam, alone.lam)
            np.testing.assert_array_equal(mixed.halfwidth, alone.halfwidth)


def test_service_components_answers_immediately():
    """Fixed-point requests are answered at admission without occupying
    a slot, even when every slot is busy."""
    svc = BCService({"web": _graph()}, n_slots=1)
    svc.submit(BCRequest(rid=0, graph="web", eps=0.02, delta=0.1))
    svc.step()  # rid 0 occupies the only slot
    assert svc.active == 1
    svc.submit(BCRequest(rid=1, graph="web", metric="components"))
    svc.step()
    done = {r.rid for r in svc.finished}
    assert 1 in done  # answered while the slot was still busy
    cc = next(r for r in svc.finished if r.rid == 1)
    ref = cc_ref(_graph())
    ids = np.argsort(ref)[::-1][:10]
    np.testing.assert_array_equal(cc.lam, ref[ids])
    assert cc.converged and np.all(cc.halfwidth == 0.0)
    svc.run()  # drain rid 0 cleanly


def test_service_plan_records_metric():
    """Each non-default request's per-request plan carries its metric —
    the bench's per-metric plan evidence."""
    out = _serve([BCRequest(rid=0, graph="web", eps=0.1, delta=0.1,
                            metric="closeness")])
    d = out[0].plan.to_json()
    assert d["metric"] == "closeness"
