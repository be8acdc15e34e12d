"""Unified ``repro.bc`` solver API: planner decisions, BCPlan contents,
exact-vs-approx parity through both executors, and the deprecation shims.

The multi-device half of the planner contract (8 visible devices → mesh
placement, auto-built MeshExecutor, mesh-vs-host parity) runs in a
subprocess: ``md_bc_planner_check.py``, alongside the moments check.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic sweep, see tests/_hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, strategies as st

from repro.bc import (Backend, BCPlanner, BCQuery, ExecutionConfig,
                      MeshExecutor, SingleHostExecutor, backend_spec,
                      build_executor, plan, registered_backends, solve)
from repro.core import brandes_bc
from repro.graphs.generators import from_spec, ring_of_cliques
from repro.spgemm.cost_model import Calibration, StepRates


@pytest.fixture(scope="module")
def small_graph():
    g = from_spec("rmat", scale=6, degree=8, seed=5)
    g, _ = g.remove_isolated()
    return g, brandes_bc(g)


def _mesh_1x1():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


# ---------------------------------------------------------------- planner
def test_planner_single_host_on_one_device(small_graph):
    g, _ = small_graph
    pl = BCPlanner().plan(g, BCQuery(mode="approx"), n_devices=1)
    assert pl.placement == "single_host"
    assert pl.mesh_axes is None and pl.n_devices == 1
    assert pl.predicted_comm_bytes == 0.0  # no collectives on one host
    assert pl.backend in ("dense", "coo", "csr") and pl.n_b >= 1


def test_planner_mesh_on_eight_devices(small_graph):
    """The §6.2 search picks a (pod, data, model) decomposition for p=8."""
    g, _ = small_graph
    pl = BCPlanner().plan(g, BCQuery(mode="exact"), n_devices=8)
    assert pl.placement == "mesh"
    axes = pl.axes_dict()
    assert axes == {"pod": 2, "data": 2, "model": 2}
    assert pl.backend == "dense"  # the distributed step is dense-only
    assert pl.predicted_comm_bytes > 0.0
    assert pl.predicted_mem_bytes < BCPlanner().plan(
        g, BCQuery(mode="exact"), n_devices=1).predicted_mem_bytes


def test_planner_respects_overrides_and_budget(small_graph):
    g, _ = small_graph
    pl = BCPlanner().plan(
        g, BCQuery(mode="approx", n_b=16,
                   execution=ExecutionConfig(backend="coo")),
        n_devices=1)
    assert pl.n_b == 16 and pl.backend == "coo"
    assert pl.execution.resolved and pl.execution.backend is Backend.COO
    # a pinned COO backend has no distributed step: auto-placement must
    # stay on one host even with devices available — and never silently:
    # the fallback is warned and carried on plan.notes
    with pytest.warns(UserWarning, match="no distributed step"):
        pl8 = BCPlanner().plan(
            g, BCQuery(mode="approx",
                       execution=ExecutionConfig(backend=Backend.COO)),
            n_devices=8)
    assert pl8.placement == "single_host"
    assert any("falling back to single_host" in n for n in pl8.notes)
    assert pl8.to_json()["notes"] == list(pl8.notes)
    # ... but an explicit mesh pin with COO is a hard error, not a fallback
    with pytest.raises(ValueError, match="single-host only"):
        BCPlanner().plan(
            g, BCQuery(mode="approx",
                       execution=ExecutionConfig(backend="coo",
                                                 placement="mesh")),
            n_devices=8)
    # exact budget is the full sweep; approx budget is the Hoeffding cap
    e = BCPlanner().plan(g, BCQuery(mode="exact"), n_devices=1)
    a = BCPlanner().plan(g, BCQuery(mode="approx", eps=0.1, delta=0.1,
                                    max_samples=50), n_devices=1)
    assert e.sample_budget == g.n
    assert a.sample_budget == 50
    assert e.n_batches == -(-g.n // e.n_b)


def test_plan_is_json_serializable(small_graph):
    g, _ = small_graph
    pl = plan(g, BCQuery(mode="approx", topk=5), n_devices=8)
    d = json.loads(json.dumps(pl.to_json()))
    assert d["placement"] == "mesh"
    assert d["mesh_axes"] == {"pod": 2, "data": 2, "model": 2}
    assert d["regime"]["regime"] in ("dense", "coo", "csr")
    assert "single_host" in pl.summary() or "mesh" in pl.summary()


def test_query_validation():
    with pytest.raises(ValueError):
        BCQuery(mode="both")
    with pytest.raises(ValueError):
        BCQuery(mode="approx", eps=0.0)
    with pytest.raises(ValueError):
        BCQuery(rule="gaussian")
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            BCQuery(backend="hyper")
    with pytest.raises(ValueError):
        ExecutionConfig(backend="hyper")
    with pytest.raises(ValueError):
        ExecutionConfig(placement="cluster")
    with pytest.raises(ValueError, match="conflicting"):
        BCQuery(execution=ExecutionConfig(backend="coo"), backend="dense")


def test_legacy_kwargs_shim_matches_execution_config(small_graph):
    """The stringly-typed (backend, use_kernel, block) kwargs warn and
    resolve to the exact plan the typed ExecutionConfig produces."""
    g, _ = small_graph
    with pytest.warns(DeprecationWarning, match="ExecutionConfig"):
        q_old = BCQuery(mode="approx", backend="coo", use_kernel=False,
                        block=256)
    q_new = BCQuery(mode="approx",
                    execution=ExecutionConfig(backend="coo",
                                              use_kernel=False, block=256))
    assert q_old.execution == q_new.execution
    assert q_old.backend is Backend.COO and q_old.block == 256
    pl_old = BCPlanner().plan(g, q_old, n_devices=1)
    pl_new = BCPlanner().plan(g, q_new, n_devices=1)
    assert pl_old == pl_new
    # round-trips (dataclasses.replace re-passes the mirrored fields
    # next to execution=) stay silent
    import dataclasses as _dc
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        q2 = _dc.replace(q_new, n_b=32)
    assert q2.execution == q_new.execution and q2.n_b == 32


def test_backend_registry():
    assert set(registered_backends()) == {Backend.DENSE, Backend.COO,
                                          Backend.CSR}
    assert backend_spec("dense").placements == ("single_host", "mesh")
    assert backend_spec(Backend.COO).placements == ("single_host",)
    assert backend_spec("csr").placements == ("single_host",)
    assert backend_spec("dense").supports_kernel
    assert not backend_spec("coo").supports_kernel
    assert not backend_spec("csr").supports_kernel
    with pytest.raises(ValueError):
        backend_spec("hyper")


# ------------------------------------------------------------- executors
def test_build_executor_matches_plan(small_graph):
    g, _ = small_graph
    ex = build_executor(g, plan(g, BCQuery(), n_devices=1))
    assert isinstance(ex, SingleHostExecutor)
    mesh = _mesh_1x1()
    exm = build_executor(g, plan(g, BCQuery(n_b=16, iters=32), mesh=mesh),
                         mesh=mesh)
    assert isinstance(exm, MeshExecutor)
    # the shared protocol: same (S1, S2, n_reach) from identical batches
    rng = np.random.default_rng(0)
    src = rng.integers(0, g.n, 16).astype(np.int32)
    val = np.ones(16, bool)
    s1a, s2a, nra = ex.step(src, val)
    s1b, s2b, nrb = exm.step(src, val)
    np.testing.assert_allclose(s1a, s1b, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(s2a, s2b, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(nra, np.asarray(nrb))
    # the Σδ-only exact reduction agrees with the moments S1 on both
    np.testing.assert_allclose(ex.step_sum(src, val), s1a,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(exm.step_sum(src, val), s1b,
                               rtol=1e-4, atol=1e-6)


def test_executor_rejects_oversized_batch(small_graph):
    """step() must never silently truncate a too-large batch."""
    g, _ = small_graph
    ex = build_executor(g, plan(g, BCQuery(mode="exact", n_b=16),
                                n_devices=1))
    with pytest.raises(ValueError, match="exceeds"):
        ex.step(np.arange(17, dtype=np.int32), np.ones(17, bool))


# ------------------------------------------------------ solve: both modes
def test_exact_solve_single_host_matches_oracle(small_graph):
    g, ref = small_graph
    res = solve(g, BCQuery(mode="exact"))
    np.testing.assert_allclose(res.lam, ref, rtol=1e-4, atol=1e-6)
    assert res.converged and res.approx is None
    assert res.n_samples == g.n


def test_exact_solve_mesh_matches_oracle(small_graph):
    g, ref = small_graph
    res = solve(g, BCQuery(mode="exact", n_b=16, iters=32), mesh=_mesh_1x1())
    np.testing.assert_allclose(res.lam, ref, rtol=1e-4, atol=1e-6)
    assert res.plan.placement == "mesh"


def test_exact_solve_restricted_sources(small_graph):
    """The checkpoint-resume hook: a partial sweep is a partial λ sum."""
    g, ref = small_graph
    q = BCQuery(mode="exact", n_b=16)
    head = solve(g, q, sources=np.arange(16, dtype=np.int32))
    tail = solve(g, q, sources=np.arange(16, g.n, dtype=np.int32))
    np.testing.assert_allclose(head.lam + tail.lam, ref,
                               rtol=1e-4, atol=1e-6)
    # n_samples reports what was actually swept, not the full budget
    assert head.n_samples == 16 and tail.n_samples == g.n - 16


def test_bc_run_checkpoint_resume(tmp_path, restore_compile_cache):
    """CLI resume: cumulative λ checkpoints + persisted nb survive a kill."""
    import shutil

    from repro.launch import bc_run
    from repro.launch import checkpoint as ckpt_lib

    ck = str(tmp_path / "ck")
    args = ["--graph", "rmat", "--scale", "5", "--nb", "8",
            "--ckpt-dir", ck, "--verify"]
    bc_run.main(args)  # full run; saves cumulative λ at global steps
    # simulate a kill after global batch 1: drop the later checkpoints
    for s in ckpt_lib.all_steps(ck):
        if s > 1:
            shutil.rmtree(os.path.join(ck, f"step_{s:010d}"))
    bc_run.main(args)  # resumes at batch 2; --verify checks final λ
    # a resume with a mismatched --nb must refuse, not misalign sources
    with pytest.raises(SystemExit, match="mismatches checkpoint"):
        bc_run.main(["--graph", "rmat", "--scale", "5", "--nb", "4",
                     "--ckpt-dir", ck])


def test_approx_solve_converges_within_eps_both_executors(small_graph):
    """Exact-vs-approx parity through one entry point on both executors."""
    g, ref = small_graph
    eps = 0.05
    norm = g.n * (g.n - 2)
    host = solve(g, BCQuery(mode="approx", eps=eps, delta=0.1,
                            rule="bernstein", seed=0))
    assert host.approx.converged
    assert np.abs(host.lam - ref).max() / norm <= eps
    mesh_out = solve(g, BCQuery(mode="approx", eps=eps, delta=0.1,
                                rule="bernstein", seed=0, iters=32),
                     mesh=_mesh_1x1())
    assert mesh_out.approx.converged
    assert np.abs(mesh_out.lam - ref).max() / norm <= eps
    # same seed + same n_b → identical sample sequence → identical λ̂
    if host.plan.n_b == mesh_out.plan.n_b:
        np.testing.assert_allclose(mesh_out.lam, host.lam,
                                   rtol=1e-4, atol=1e-6)


def test_solve_reuses_prebuilt_executor(small_graph):
    """Serving pattern: one executor, many queries."""
    g, ref = small_graph
    pl = plan(g, BCQuery(mode="approx"), n_devices=1)
    ex = build_executor(g, pl)
    a = solve(g, BCQuery(mode="approx", eps=0.1, delta=0.1, seed=1),
              executor=ex)
    b = solve(g, BCQuery(mode="approx", eps=0.1, delta=0.1, seed=1),
              executor=ex)
    np.testing.assert_array_equal(a.lam, b.lam)
    assert a.plan is pl


def test_topk_through_facade(small_graph):
    g, ref = small_graph
    k = 10
    res = solve(g, BCQuery(mode="approx", eps=0.05, delta=0.1,
                           rule="normal", topk=k, seed=0))
    top_ref = set(np.argsort(ref)[::-1][:k].tolist())
    assert len(top_ref & set(res.topk(k).tolist())) / k >= 0.9


# ------------------------------------------------------ deprecation shims
def test_approx_bc_shim_warns_and_matches(small_graph):
    g, _ = small_graph
    from repro.approx import approx_bc

    # the shim's historical defaults pin (dense, no kernel) — the ref
    # must pin the same config, since an unpinned query is now free to
    # route to the calibrated COO fast path
    ref = solve(g, BCQuery(mode="approx", eps=0.1, delta=0.1,
                           rule="normal", seed=4,
                           execution=ExecutionConfig(backend="dense",
                                                     use_kernel=False))
                ).approx
    with pytest.warns(DeprecationWarning, match="repro.bc.solve"):
        old = approx_bc(g, eps=0.1, delta=0.1, rule="normal", seed=4)
    np.testing.assert_array_equal(old.lam, ref.lam)
    np.testing.assert_array_equal(old.halfwidth, ref.halfwidth)
    assert (old.n_samples, old.n_epochs, old.converged) == \
        (ref.n_samples, ref.n_epochs, ref.converged)


def test_dist_mfbc_shim_warns_and_matches(small_graph):
    g, _ = small_graph
    from repro.core.dist_bc import dist_mfbc

    mesh = _mesh_1x1()
    ref = solve(g, BCQuery(mode="exact", n_b=16, iters=32,
                           execution=ExecutionConfig(use_kernel=False)),
                mesh=mesh)
    with pytest.warns(DeprecationWarning, match="repro.bc.solve"):
        old = dist_mfbc(g, mesh, nb=16, iters=32)
    np.testing.assert_array_equal(old, ref.lam)


# ------------------------------------------------------------ service path
def test_service_exposes_plan(small_graph):
    from repro.serve.bc_service import BCRequest, BCService

    g, ref = small_graph
    svc = BCService({"web": g, "ring": ring_of_cliques(4, 5)}, n_slots=2)
    pl = svc.plan_for("web")
    assert pl.placement == "single_host" and pl.mode == "approx"
    svc.submit(BCRequest(rid=0, graph="web", k=5, rule="normal"))
    out = svc.run()
    assert len(out) == 1 and out[0].converged
    top_ref = set(np.argsort(ref)[::-1][:5].tolist())
    assert len(top_ref & set(out[0].topk)) >= 4


# -------------------------------------------- calibrated backend routing
def _coo_wins_calibration():
    """Synthetic measured rates where COO is ~20× faster per relax and
    the Pallas kernel loses to the jnp fallback (the CPU CI verdict)."""
    return Calibration(rates={
        "dense": StepRates(ops_per_s=4e9, overhead_s=0.0),
        "dense_kernel": StepRates(ops_per_s=3e9, overhead_s=0.1),
        "coo": StepRates(ops_per_s=3e9, overhead_s=0.05),
    }, meta={"jax_backend": "test"})


def test_calibrated_plan_routes_to_coo_backend():
    """Regression for the hard-pinned dense path: a scale-10 R-MAT plan
    whose calibrated regime record says COO must actually select the COO
    backend (and record why)."""
    g = from_spec("rmat", scale=10, degree=16, seed=7)
    g, _ = g.remove_isolated()
    planner = BCPlanner(calibration=_coo_wins_calibration())
    pl = planner.plan(g, BCQuery(mode="approx"), n_devices=1)
    assert pl.regime["calibrated"] is True
    assert pl.regime["regime"] == "coo"
    assert pl.backend == "coo"
    assert pl.execution.backend is Backend.COO
    assert pl.use_kernel is False  # kernel measured slower: stays off
    assert pl.predicted_step_seconds == pytest.approx(pl.regime["coo_s"])


def test_calibrated_kernel_verdict_lights_up_pallas():
    """Where the calibration measured the Pallas dense kernel faster,
    an unpinned dense plan resolves use_kernel=True; a pin still wins."""
    cal = Calibration(rates={
        # dense dominates COO; kernel beats the jnp fallback
        "dense": StepRates(ops_per_s=4e9),
        "dense_kernel": StepRates(ops_per_s=9e9),
        "coo": StepRates(ops_per_s=1e6),
    })
    assert cal.kernel_pays()
    g = from_spec("rmat", scale=6, degree=8, seed=5)
    g, _ = g.remove_isolated()
    planner = BCPlanner(calibration=cal)
    pl = planner.plan(g, BCQuery(mode="approx"), n_devices=1)
    assert pl.backend == "dense" and pl.use_kernel is True
    assert pl.predicted_step_seconds == pytest.approx(
        pl.regime["dense_kernel_s"])
    pinned = planner.plan(
        g, BCQuery(mode="approx",
                   execution=ExecutionConfig(use_kernel=False)),
        n_devices=1)
    assert pinned.backend == "dense" and pinned.use_kernel is False


# ------------------------------------------- COO vs dense executor parity
@st.composite
def rmat_graphs(draw):
    scale = draw(st.integers(min_value=5, max_value=7))
    degree = draw(st.integers(min_value=4, max_value=10))
    seed = draw(st.integers(min_value=0, max_value=2 ** 31 - 1))
    g = from_spec("rmat", scale=scale, degree=degree, seed=seed)
    g, _ = g.remove_isolated()
    return g


@settings(max_examples=10, deadline=None)
@given(rmat_graphs(), st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_coo_dense_executor_parity_on_random_rmat(g, batch_seed):
    """The parity oracle at executor level: COO-backend step and
    step_segmented moments must match the dense backend on random R-MAT
    graphs to the documented tolerance (both reduce exact per-source
    dependencies in float32; op order differs, so bitwise equality is
    not guaranteed — rtol=1e-4/atol=1e-6, same as kernels/ref.py)."""
    nb = 8
    execs = {}
    for be in ("dense", "coo", "csr"):
        pl = BCPlanner(calibration=None).plan(
            g, BCQuery(mode="approx", n_b=nb,
                       execution=ExecutionConfig(backend=be)),
            n_devices=1)
        assert pl.backend == be
        execs[be] = build_executor(g, pl)
    rng = np.random.default_rng(batch_seed)
    src = rng.integers(0, g.n, nb).astype(np.int32)
    val = np.ones(nb, bool)
    d1, d2, dn = execs["dense"].step(src, val)
    for be in ("coo", "csr"):
        c1, c2, cn = execs[be].step(src, val)
        np.testing.assert_allclose(c1, d1, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(c2, d2, rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(cn), np.asarray(dn))
    # fused slotted variant: same tolerance, per slot
    sid = np.sort(rng.integers(0, 2, nb)).astype(np.int32)
    ds = execs["dense"].step_segmented(src, val, sid, 2)
    for be in ("coo", "csr"):
        cs = execs[be].step_segmented(src, val, sid, 2)
        np.testing.assert_allclose(cs[0], ds[0], rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(cs[1], ds[1], rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(cs[2]), np.asarray(ds[2]))


def test_fused_equals_unfused_per_backend(small_graph):
    """The PR 4 bitwise fused-vs-unfused property, per backend: slot j of
    a fused step_segmented equals an unfused one-slot step_segmented over
    exactly slot j's rows (same segment-sum accumulation path → bitwise),
    on BOTH executors' backends."""
    g, _ = small_graph
    rng = np.random.default_rng(3)
    for be in ("dense", "coo", "csr"):
        pl = BCPlanner(calibration=None).plan(
            g, BCQuery(mode="approx", n_b=16,
                       execution=ExecutionConfig(backend=be)),
            n_devices=1)
        ex = build_executor(g, pl)
        src = rng.integers(0, g.n, 16).astype(np.int32)
        val = np.ones(16, bool)
        sid = np.repeat(np.arange(2, dtype=np.int32), 8)
        s1, s2, nr = ex.step_segmented(src, val, sid, 2)
        for slot in range(2):
            rows = src[sid == slot]
            u1, u2, un = ex.step_segmented(
                rows, np.ones(rows.shape[0], bool),
                np.zeros(rows.shape[0], np.int32), 1)
            np.testing.assert_array_equal(np.asarray(s1)[slot],
                                          np.asarray(u1)[0])
            np.testing.assert_array_equal(np.asarray(s2)[slot],
                                          np.asarray(u2)[0])
            np.testing.assert_array_equal(np.asarray(nr)[slot],
                                          np.asarray(un)[0])


# --------------------------------------------- frontier-sparse CSR backend
def test_csr_solve_attaches_occupancy_trace(small_graph):
    """A pinned-CSR solve records the frontier-occupancy side channel on
    its result; the plan stays the decision alone (and passes through
    solve by identity — see test_solve_reuses_prebuilt_executor)."""
    g, ref = small_graph
    q = BCQuery(mode="exact", n_b=16,
                execution=ExecutionConfig(backend="csr"))
    pl = plan(g, q, n_devices=1)
    res = solve(g, q, plan=pl)
    np.testing.assert_allclose(res.lam, ref, rtol=1e-4, atol=1e-6)
    assert res.plan is pl
    occ = res.occupancy
    assert occ is not None and occ["batches"] >= 1
    assert occ["per_iter_bf"] and occ["relax_calls"] > 0
    assert occ["fnnz_first"] >= occ["fnnz_last"]
    assert 0.0 <= occ["hit_rate"] <= 1.0
    assert 0 < occ["frontier_arcs"] <= occ["arc_slots"]
    # occupancy is plain JSON, and the plan's wire form never carries it
    from repro.bc.planner import BCPlan
    assert json.loads(json.dumps(occ)) == occ
    d = json.loads(json.dumps(res.plan.to_json()))
    assert "occupancy" not in d
    assert BCPlan.from_json(d) == pl
    # records that still carry the key load, and drop it
    assert BCPlan.from_json(dict(d, occupancy=occ)) == pl
    # dense solves keep no occupancy
    dense = solve(g, BCQuery(mode="exact", n_b=16,
                             execution=ExecutionConfig(backend="dense")))
    assert dense.occupancy is None


def test_dense_relax_cp_transpose_is_hoisted(small_graph):
    """Satellite 2: ``DenseAdj.relax_cp`` must use the prebuilt Aᵀ pytree
    leaf — no per-call 2D transpose of the (n, n) adjacency may appear in
    the traced program. (The monoid scan's 3D ``moveaxis`` over the
    frontier stack is expected and allowed.)"""
    import jax

    from repro.core.adjacency import dense_adj_from_graph
    from repro.core.mfbf import mfbf

    g, _ = small_graph
    adj = dense_adj_from_graph(g, block=64, use_kernel=False)
    assert adj.at is not None
    np.testing.assert_array_equal(np.asarray(adj.at), np.asarray(adj.a).T)

    from repro.core import monoids

    F = monoids.centpath_identity((4, g.n))
    jaxpr = jax.make_jaxpr(adj.relax_cp)(F)

    def _has_2d_transpose(jpr):
        for eqn in jpr.eqns:
            if eqn.primitive.name == "transpose":
                perm = eqn.params.get("permutation")
                if tuple(perm) == (1, 0):
                    return True
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    if _has_2d_transpose(sub.jaxpr):
                        return True
        return False

    assert not _has_2d_transpose(jaxpr.jaxpr), \
        "relax_cp still transposes the adjacency per call"
    # the hoisted transpose computes the same thing end to end
    src = np.arange(4, dtype=np.int32)
    np.testing.assert_array_equal(
        np.asarray(mfbf(adj, src)[0]),
        np.asarray(mfbf(dense_adj_from_graph(g, block=64), src)[0]))


# ------------------------------------------------------------ multi-device
@pytest.mark.slow
def test_multidevice_planner_subprocess():
    """8 visible devices: auto mesh plan + solve parity (subprocess)."""
    script = os.path.join(os.path.dirname(__file__),
                          "md_bc_planner_check.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL-OK" in out.stdout
