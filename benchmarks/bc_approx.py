"""Exact-vs-approximate BC benchmark (the new sampling workload).

Both legs now run through the unified solver API: one
``repro.bc.solve(graph, BCQuery(...))`` call per leg, with the chosen
``BCPlan`` (backend, n_b, placement, predicted cost) recorded next to
the timings — the perf trajectory captures planner decisions, not just
seconds. Reports

* ``speedup``        — t_exact / t_approx (both jit-warm),
* ``topk_precision`` — |top-k(exact) ∩ top-k(approx)| / k,
* ``spearman``       — rank correlation of λ̂ vs λ over all vertices,
* ``max_norm_err``   — max_v |λ̂ − λ| / (n·(n−2)), comparable to ε,
* ``plan`` / ``mesh_epochs.*.plan`` — the executed ``BCPlan`` records,
* ``backends``      — the self-calibrated dense/COO/CSR race: the run
  refits ``results/cost_calibration.json`` on its own graph, then times
  pinned dense, pinned COO, pinned frontier-sparse CSR and
  planner-routed (``auto``) legs over a fixed uniform sample budget,
  recording each executed plan next to its ``measured_seconds`` — the
  CSR leg's plan carries the frontier-occupancy trace
  (``tools/check_bench.py`` gates prediction drift at 2×, that ``auto``
  lands on a sparse backend, and that CSR beats pinned COO),

plus a mesh-vs-single-host *epoch* comparison (``mesh_epochs`` record):
both paths run the same adaptive estimator — the mesh step returns fused
(Σδ, Σδ²) — so the numbers to watch are epochs-to-converge and
``samples_saved`` vs the fixed Hoeffding budget. Fewer sampling epochs =
fewer distributed SpGEMM rounds for the same (ε, δ) guarantee.

Everything lands in ``BENCH_approx.json`` (consumed as a CI artifact;
``benchmarks.run`` prints the same numbers as CSV rows).

  PYTHONPATH=src python -m benchmarks.bc_approx             # scale 10
  PYTHONPATH=src python -m benchmarks.bc_approx --smoke     # scale 8, CI
  PYTHONPATH=src python -m benchmarks.bc_approx --mesh 2x2  # 4 devices
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Dict, Tuple

import numpy as np


def _spearman(a: np.ndarray, b: np.ndarray) -> float:
    ra = np.argsort(np.argsort(a)).astype(np.float64)
    rb = np.argsort(np.argsort(b)).astype(np.float64)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = np.sqrt((ra * ra).sum() * (rb * rb).sum())
    return float((ra * rb).sum() / denom) if denom > 0 else 1.0


def bench_bc_approx(scale: int = 10, degree: int = 8, eps: float = 0.05,
                    delta: float = 0.1, k: int = 10, nb: int = 64,
                    rule: str = "normal", seed: int = 0) -> Dict:
    """One exact-vs-approx comparison; returns the BENCH record."""
    from repro.bc import BCQuery, ExecutionConfig, solve
    from repro.bc import plan as bc_plan
    from repro.graphs.generators import from_spec

    g = from_spec("rmat", scale=scale, degree=degree, seed=seed)
    g, _ = g.remove_isolated()

    # backend/n_b/placement pinned (comparability with earlier BENCH
    # records, and fake mesh devices must not reroute the headline legs);
    # the plan's ``regime`` field still records the planner's unpinned
    # dense-vs-COO opinion. The dense-vs-COO wall-clock race itself is
    # ``bench_backends`` below.
    dense = ExecutionConfig(backend="dense")
    exact_q = BCQuery(mode="exact", n_b=nb, execution=dense)
    approx_q = BCQuery(mode="approx", eps=eps, delta=delta, rule=rule,
                       n_b=nb, execution=dense, topk=k, seed=seed)
    exact_pl = bc_plan(g, exact_q, n_devices=1)
    approx_pl = bc_plan(g, approx_q, n_devices=1)

    # jit warm-up for both paths (one small restricted run each), so the
    # timed section measures steady-state batch throughput, not XLA.
    solve(g, exact_q, plan=exact_pl, sources=np.arange(nb, dtype=np.int32))
    solve(g, dataclasses.replace(approx_q, max_samples=nb, seed=seed + 1),
          plan=approx_pl)

    t0 = time.time()
    exact = solve(g, exact_q, plan=exact_pl)
    t_exact = time.time() - t0

    t0 = time.time()
    out = solve(g, approx_q, plan=approx_pl)
    t_approx = time.time() - t0
    res = out.approx

    top_exact = set(exact.topk(k).tolist())
    top_approx = set(res.topk(k).tolist())
    norm = g.n * max(g.n - 2, 1)
    record = {
        "name": f"bc_approx_rmat_s{scale}_e{degree}",
        "n": g.n,
        "m": g.m,
        "eps": eps,
        "delta": delta,
        "rule": rule,
        "k": k,
        "n_samples": res.n_samples,
        "n_epochs": res.n_epochs,
        "converged": res.converged,
        "seconds_exact": t_exact,
        "seconds_approx": t_approx,
        "speedup": t_exact / max(t_approx, 1e-9),
        "sample_frac": res.n_samples / g.n,
        "topk_precision": len(top_exact & top_approx) / k,
        "spearman": _spearman(exact.lam, res.lam),
        "max_norm_err": float(np.abs(res.lam - exact.lam).max()) / norm,
        "plan": out.plan.to_json(),
        "plan_exact": exact.plan.to_json(),
    }
    return record


def bench_backends(scale: int = 10, degree: int = 8, eps: float = 0.05,
                   delta: float = 0.1, nb: int = 64, seed: int = 0) -> Dict:
    """Dense/COO/CSR executor race, planned with a fresh calibration.

    The ISSUE-6 measurement loop, end to end: (1) refit the α-β step
    constants on this benchmark's own graph (``repro.launch.calibrate``)
    and persist them to ``results/cost_calibration.json`` — the planner's
    ``"auto"`` calibration reloads the file mid-process via its
    mtime-keyed cache, so every leg below plans with the rates just
    measured (and future CLI runs inherit them); (2) run the same
    fixed-budget uniform-sampling query once per pinned backend and once
    unpinned (``auto`` — the calibrated regime routing), recording the
    executed ``BCPlan`` *with* its measured wall-clock next to
    ``predicted_seconds``. The budget is a fixed ``4·n_b`` samples
    (uniform strategy → exactly 4 batches, no adaptive early stop), so
    ``measured_seconds`` times exactly the work the plan priced —
    ``tools/check_bench.py`` gates the prediction drift at 2× and
    asserts the auto leg actually lights up a sparse fast path. The
    pinned CSR leg's plan record additionally carries the solve's
    frontier-occupancy trace (per-iteration frontier nnz, compaction
    hit rate, overflow count) under ``plan.occupancy``.
    """
    from repro.bc import BCQuery, ExecutionConfig, solve
    from repro.bc import plan as bc_plan
    from repro.graphs.generators import from_spec
    from repro.launch.calibrate import calibrate
    from repro.spgemm.cost_model import save_calibration

    g = from_spec("rmat", scale=scale, degree=degree, seed=seed)
    g, _ = g.remove_isolated()

    cal = calibrate(g, nb_pair=(max(nb // 4, 8), nb), reps=2,
                    variants=(("dense", False), ("coo", False),
                              ("csr", False)))
    cal_path = save_calibration(cal)

    budget = 4 * nb
    legs: Dict[str, Dict] = {}
    for leg in ("dense", "coo", "csr", "auto"):
        execution = ExecutionConfig(backend=None if leg == "auto" else leg)
        q = BCQuery(mode="approx", eps=eps, delta=delta, rule="normal",
                    n_b=nb, strategy="uniform", max_samples=budget,
                    seed=seed, execution=execution)
        pl = bc_plan(g, q, n_devices=1)
        # jit warm-up (one batch) so the timed run is steady-state
        solve(g, dataclasses.replace(q, max_samples=nb, seed=seed + 1),
              plan=pl)
        t0 = time.time()
        out = solve(g, q, plan=pl)
        dt = time.time() - t0
        legs[leg] = {
            "backend": out.plan.backend,
            "calibrated": bool(out.plan.regime.get("calibrated")),
            "n_samples": out.approx.n_samples,
            "measured_seconds": dt,
            "predicted_seconds": out.plan.predicted_seconds,
            "prediction_ratio": out.plan.predicted_seconds / max(dt, 1e-9),
            # the executor's occupancy counters ride in the plan record,
            # where tools/check_bench.py reads them
            "plan": (out.plan.to_json() if out.occupancy is None else
                     dict(out.plan.to_json(), occupancy=out.occupancy)),
        }
    return {
        "n": g.n,
        "m": g.m,
        "sample_budget": budget,
        "calibration_path": cal_path,
        "calibration": cal.to_json(),
        "coo_speedup": (legs["dense"]["measured_seconds"]
                        / max(legs["coo"]["measured_seconds"], 1e-9)),
        "csr_speedup": (legs["coo"]["measured_seconds"]
                        / max(legs["csr"]["measured_seconds"], 1e-9)),
        **legs,
    }


def _parse_mesh_dims(spec: str) -> Tuple[int, ...]:
    """Axis sizes of a ``DxM`` / ``PxDxM`` spec, jax-free.

    ``main`` must know the device count *before* anything imports jax
    (to set XLA_FLAGS); ``repro.launch.mesh.parse_mesh_spec`` imports
    jax only lazily inside the mesh constructors, so this is safe."""
    from repro.launch.mesh import parse_mesh_spec

    try:
        dims, _ = parse_mesh_spec(spec)
    except ValueError as e:
        raise SystemExit(f"--mesh: {e}")
    return dims


def bench_mesh_epochs(scale: int = 10, degree: int = 8, eps: float = 0.05,
                      delta: float = 0.1, nb: int = 64, rule: str = "normal",
                      seed: int = 0, mesh_shape: Tuple[int, ...] = (1, 1),
                      iters: int = 64) -> Dict:
    """Adaptive stopping on the mesh path vs single host vs Hoeffding.

    Runs the same (ε, δ) adaptive estimator through the single-host
    moments executor and the distributed mesh moments executor, and
    reports for each: epochs-to-converge, samples drawn, the executed
    ``BCPlan`` and ``samples_saved`` — how far under the fixed Hoeffding
    budget the empirical-Bernstein/CLT stopping rule got.

    Timing caveat: the single-host leg is jit-warmed (one capped run)
    so its ``seconds`` is steady-state, but the mesh leg's ``seconds``
    necessarily includes step preparation + shard_map compilation —
    the mesh executor is built fresh per solve call, so that cost is
    paid by every real caller and excluding it would flatter the mesh
    path. Epochs and samples are the apples-to-apples comparison;
    seconds are per-path end-to-end latencies.
    """
    import jax

    from repro.approx import hoeffding_budget
    from repro.bc import BCQuery, ExecutionConfig, solve
    from repro.graphs.generators import from_spec

    g = from_spec("rmat", scale=scale, degree=degree, seed=seed)
    g, _ = g.remove_isolated()
    names = (("data", "model") if len(mesh_shape) == 2
             else ("pod", "data", "model"))
    need = 1
    for d in mesh_shape:
        need *= d
    n_dev = len(jax.devices())
    if need != n_dev:
        raise SystemExit(f"mesh shape {mesh_shape} needs {need} devices, "
                         f"jax sees {n_dev}")
    mesh = jax.make_mesh(mesh_shape, names)
    budget = hoeffding_budget(g.n, eps, delta)
    base_q = BCQuery(mode="approx", eps=eps, delta=delta, rule=rule,
                     n_b=nb, execution=ExecutionConfig(backend="dense"),
                     seed=seed)

    from repro.bc import plan as bc_plan

    # pin the single-host leg's placement: with fake devices visible the
    # planner would otherwise route both legs through the mesh
    host_plan = bc_plan(g, base_q, n_devices=1)

    # jit warm-up for the single-host executor (the mesh executor compiles
    # per call — see the timing caveat above).
    solve(g, dataclasses.replace(base_q, max_samples=nb, seed=seed + 1),
          plan=host_plan)

    def one(tag, q=base_q, **kw):
        t0 = time.time()
        out = solve(g, q, **kw)
        res = out.approx
        return {
            "path": tag,
            "n_samples": res.n_samples,
            "n_epochs": res.n_epochs,
            "converged": res.converged,
            "has_moments": res.has_moments,
            "samples_saved": budget - res.n_samples,
            "seconds": time.time() - t0,
            # the executor's occupancy counters ride in the plan record,
            # where tools/check_bench.py reads them
            "plan": (out.plan.to_json() if out.occupancy is None else
                     dict(out.plan.to_json(), occupancy=out.occupancy)),
        }

    host = one("single_host", plan=host_plan)
    dist = one("mesh", q=dataclasses.replace(base_q, iters=iters), mesh=mesh)
    return {
        "n": g.n,
        "m": g.m,
        "eps": eps,
        "delta": delta,
        "rule": rule,
        "mesh_shape": list(mesh_shape),
        "hoeffding_budget": budget,
        "hoeffding_epochs": -(-budget // nb),
        "single_host": host,
        "mesh": dist,
    }


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--eps", type=float, default=0.05)
    ap.add_argument("--delta", type=float, default=0.1)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--nb", type=int, default=64)
    ap.add_argument("--rule", default="normal",
                    choices=["normal", "bernstein"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_approx.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (scale 8)")
    ap.add_argument("--mesh", default="1x1",
                    help="DxM or PxDxM axis sizes for the epoch benchmark "
                         "(forces fake host devices when needed)")
    ap.add_argument("--mesh-iters", type=int, default=64,
                    help="static sweep bound for the mesh step")
    args = ap.parse_args(argv)

    mesh_shape = _parse_mesh_dims(args.mesh)
    n_dev = 1
    for d in mesh_shape:
        n_dev *= d
    if n_dev > 1 and "jax" not in sys.modules:
        # Must happen before jax initializes; all repro imports are lazy.
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_dev} "
            + os.environ.get("XLA_FLAGS", ""))

    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    scale = 8 if args.smoke else args.scale
    # Calibrate first: the headline legs' regime records (and any
    # unpinned routing) then price with the constants just measured.
    backends = bench_backends(scale=scale, degree=args.degree, eps=args.eps,
                              delta=args.delta, nb=args.nb, seed=args.seed)
    rec = bench_bc_approx(scale=scale, degree=args.degree, eps=args.eps,
                          delta=args.delta, k=args.k, nb=args.nb,
                          rule=args.rule, seed=args.seed)
    rec["backends"] = backends
    rec["mesh_epochs"] = bench_mesh_epochs(
        scale=scale, degree=args.degree, eps=args.eps, delta=args.delta,
        nb=args.nb, rule=args.rule, seed=args.seed, mesh_shape=mesh_shape,
        iters=args.mesh_iters)
    # Records merged in by other benchmarks (bc_scaling.py --merge) must
    # survive a rerun of this one.
    if os.path.exists(args.out):
        with open(args.out) as f:
            prev = json.load(f)
        for key in ("scaling",):
            if key in prev and key not in rec:
                rec[key] = prev[key]
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    pl = rec["plan"]
    print(f"[bc_approx] n={rec['n']} m={rec['m']} "
          f"samples={rec['n_samples']}/{rec['n']} "
          f"({rec['n_epochs']} epochs, converged={rec['converged']})")
    print(f"[bc_approx] plan: {pl['placement']} backend={pl['backend']} "
          f"n_b={pl['n_b']} predicted {pl['predicted_seconds']:.3g}s")
    print(f"[bc_approx] exact {rec['seconds_exact']:.2f}s vs approx "
          f"{rec['seconds_approx']:.2f}s — speedup {rec['speedup']:.2f}x")
    bk = rec["backends"]
    print(f"[bc_approx] backends ({bk['sample_budget']} uniform samples): "
          f"dense {bk['dense']['measured_seconds']:.2f}s vs coo "
          f"{bk['coo']['measured_seconds']:.2f}s vs csr "
          f"{bk['csr']['measured_seconds']:.2f}s — coo speedup "
          f"{bk['coo_speedup']:.2f}x, csr-over-coo "
          f"{bk['csr_speedup']:.2f}x; auto routed to "
          f"backend={bk['auto']['backend']}"
          + (" [calibrated]" if bk["auto"]["calibrated"] else ""))
    occ = bk["csr"]["plan"].get("occupancy") or {}
    if occ:
        print(f"[bc_approx]   csr occupancy: fnnz "
              f"{occ.get('fnnz_first')}→{occ.get('fnnz_last')} over "
              f"{occ.get('iters_bf')} fwd iters, hit_rate "
              f"{occ.get('hit_rate', 0.0):.2f}, "
              f"overflows {occ.get('overflows')}")
    for leg in ("dense", "coo", "csr", "auto"):
        print(f"[bc_approx]   {leg}: predicted "
              f"{bk[leg]['predicted_seconds']:.3g}s / measured "
              f"{bk[leg]['measured_seconds']:.3g}s "
              f"(ratio {bk[leg]['prediction_ratio']:.2f})")
    print(f"[bc_approx] top-{rec['k']} precision {rec['topk_precision']:.2f} "
          f"spearman {rec['spearman']:.3f} "
          f"max_norm_err {rec['max_norm_err']:.4f} (eps {rec['eps']})")
    me = rec["mesh_epochs"]
    print(f"[bc_approx] mesh {args.mesh}: "
          f"{me['mesh']['n_samples']} samples in {me['mesh']['n_epochs']} "
          f"epochs (single-host {me['single_host']['n_samples']} in "
          f"{me['single_host']['n_epochs']}) vs Hoeffding budget "
          f"{me['hoeffding_budget']} ({me['hoeffding_epochs']} epochs) — "
          f"saved {me['mesh']['samples_saved']} samples")
    print(f"[bc_approx] wrote {args.out}")
    return rec


if __name__ == "__main__":
    main()
