"""Betweenness-centrality launcher (the paper's own workload).

  PYTHONPATH=src python -m repro.launch.bc_run --graph rmat --scale 8 \
      --degree 8 --nb 64 [--weighted] [--backend auto|dense|coo] \
      [--ckpt-dir d] [--metric betweenness|closeness|khop|components] \
      [--hops k]

``--metric`` swaps the analytic computed by the sweep (the MetricSpec
registry, ``repro.core.metrics``): closeness is the forward-only farness
profile, ``khop`` (with ``--hops k``) hop-bounded reachability, and
``components`` the min-label fixed point (exact mode only, no source
sweep). ``--verify`` checks each against its own host oracle.

Every mode is one call into the unified solver API: build a
``repro.bc.BCQuery``, let ``BCPlanner`` resolve backend / batch size /
placement (printed as the ``BCPlan`` line; pin with --nb / --backend /
--mesh), and run ``repro.bc.solve``.

Per-batch checkpointing: the λ accumulator + batch index is saved after
every batch, so a killed run resumes without recomputing finished batches
(Algorithm 3's outer loop is embarrassingly restartable).

Approximate mode (adaptive source sampling, see ``repro.approx``):

  PYTHONPATH=src python -m repro.launch.bc_run --graph rmat --scale 10 \
      --approx 0.05,0.1 [--topk 10] [--strategy adaptive|uniform] \
      [--rule bernstein|normal] [--mesh DxM | PxDxM]

``--approx eps,delta`` replaces the exact all-sources sweep with the
epoch-doubling sampler and prints the top-k central vertices with their
confidence intervals.

``--mesh`` pins placement to the distributed Theorem 5.1 moments step:
``--mesh 2x4`` maps (data=2, model=4), ``--mesh 2x2x2`` maps (pod=2,
data=2, model=2). The axis-size product must equal the visible jax
device count. Without the flag the planner places automatically
(single host on one device, a (pod, data, model) decomposition when
more are visible).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.bc import METRICS, BCQuery, ExecutionConfig
from repro.bc import plan as bc_plan
from repro.bc import solve as bc_solve
from repro.core import brandes_bc, cc_ref, closeness_ref, khop_ref
from repro.graphs.generators import from_spec
from repro.launch.mesh import mesh_from_spec
from repro.launch.runtime import enable_compile_cache
from repro.launch import checkpoint as ckpt_lib


def _query_from_args(args, mode: str, **kw) -> BCQuery:
    # CLI flags map onto the typed ExecutionConfig: "auto" / an absent
    # --use-kernel leave the field None, so the planner resolves it from
    # the calibrated regime model (and the measured kernel verdict).
    execution = ExecutionConfig(
        backend=None if args.backend == "auto" else args.backend,
        use_kernel=True if args.use_kernel else None)
    try:
        return BCQuery(mode=mode, n_b=args.nb or None, execution=execution,
                       seed=args.seed, iters=args.iters, metric=args.metric,
                       hops=args.hops, **kw)
    except ValueError as e:  # e.g. --metric khop without --hops
        raise SystemExit(f"[bc] bad query: {e}")


# --verify oracles per metric (components verifies against union-find)
_REFS = {"betweenness": brandes_bc, "closeness": closeness_ref,
         "components": cc_ref}


def run_approx(args, g):
    """Adaptive-sampling approximate BC + top-k report via repro.bc."""
    try:
        eps_s, delta_s = args.approx.split(",")
        eps, delta = float(eps_s), float(delta_s)
    except ValueError:
        raise SystemExit(
            f"--approx expects 'eps,delta' (e.g. 0.05,0.1), got "
            f"{args.approx!r}")
    if not (0 < eps < 1 and 0 < delta < 1):
        raise SystemExit(f"--approx eps and delta must be in (0, 1), got "
                         f"eps={eps} delta={delta}")
    try:
        mesh = mesh_from_spec(args.mesh) if args.mesh else None
    except ValueError as e:
        raise SystemExit(f"--mesh: {e}")
    query = _query_from_args(args, "approx", eps=eps, delta=delta,
                             strategy=args.strategy, rule=args.rule,
                             topk=args.topk,
                             max_samples=args.max_samples or None)
    print(f"[bc] approx mode: eps={eps} delta={delta} "
          f"strategy={args.strategy} rule={args.rule}"
          + (f" mesh={args.mesh}" if args.mesh else ""))
    try:
        pl = bc_plan(g, query, mesh=mesh)
    except ValueError as e:  # e.g. --mesh with --backend coo
        raise SystemExit(f"[bc] cannot plan this query: {e}")
    print(f"[bc] {pl.summary()} execution={pl.execution.describe()}"
          + (" [calibrated]" if pl.regime.get("calibrated") else ""))
    for note in pl.notes:
        print(f"[bc] note: {note}")

    def progress(epoch, tau, max_hw):
        print(f"[bc] epoch {epoch}: tau={tau} max_halfwidth={max_hw:.4f}")

    t0 = time.time()
    out = bc_solve(g, query, mesh=mesh, plan=pl, progress_cb=progress)
    res = out.approx
    dt = time.time() - t0
    teps = g.m * res.n_samples / dt
    print(f"[bc] approx done in {dt:.2f}s — {res.n_samples} samples "
          f"({res.n_epochs} epochs, converged={res.converged}) — "
          f"{teps:,.0f} TEPS (model)")
    ids = res.topk(args.topk)
    print(f"[bc] top-{args.topk} central vertices (λ̂ ± CI):")
    for v in ids:
        print(f"[bc]   v={int(v):6d}  {res.lam[v]:12.2f} ± "
              f"{res.halfwidth[v]:.2f}")
    if args.verify:
        if args.metric != "betweenness":
            # Non-BC metrics have their own normalization constants; the
            # ε bound below is the BC one, so check ranking quality only.
            ref = (khop_ref(g, hops=args.hops) if args.metric == "khop"
                   else _REFS[args.metric](g))
            top_ref = set(np.argsort(ref)[::-1][:args.topk].tolist())
            prec = len(top_ref & set(ids.tolist())) / args.topk
            print(f"[bc] vs {args.metric} oracle: top-{args.topk} "
                  f"precision {prec:.2f}")
            return res
        ref = brandes_bc(g)
        norm = g.n * max(g.n - 2, 1)
        err = float(np.abs(res.lam - ref).max()) / norm
        top_ref = set(np.argsort(ref)[::-1][:args.topk].tolist())
        prec = len(top_ref & set(ids.tolist())) / args.topk
        print(f"[bc] vs Brandes oracle: max normalized error {err:.4f} "
              f"(eps={eps}), top-{args.topk} precision {prec:.2f}")
        if err > eps:
            # Legitimate with probability ≤ delta (and the "normal" rule's
            # CIs are a CLT profile, not a concentration bound) — warn,
            # don't crash.
            print(f"[bc] WARNING: error {err:.4f} exceeds eps={eps} "
                  f"(expected with probability <= {delta})")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="rmat",
                    choices=["rmat", "uniform", "er"])
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--weighted", action="store_true")
    ap.add_argument("--nb", type=int, default=0,
                    help="batch size (0 = planner's cost-model pick)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "dense", "coo"],
                    help="relax backend (auto = planner's regime choice)")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--metric", default="betweenness", choices=list(METRICS),
                    help="graph metric to solve (MetricSpec registry)")
    ap.add_argument("--hops", type=int, default=0,
                    help="hop bound (edges) for --metric khop")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="check against the Brandes oracle (slow)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--approx", default="",
                    help="eps,delta — run adaptive-sampling approximate BC")
    ap.add_argument("--topk", type=int, default=10,
                    help="top-k query size for --approx")
    ap.add_argument("--strategy", default="adaptive",
                    choices=["adaptive", "uniform"])
    ap.add_argument("--rule", default="bernstein",
                    choices=["bernstein", "normal"])
    ap.add_argument("--max-samples", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="DxM or PxDxM axis sizes — pin placement to the "
                         "distributed moments step")
    ap.add_argument("--iters", type=int, default=0,
                    help="static sweep bound for mesh placement "
                         "(0 = graph size)")
    args = ap.parse_args(argv)

    if args.mesh and not args.approx:
        raise SystemExit("--mesh requires --approx (the exact mesh sweep "
                         "is examples/bc_distributed.py)")
    enable_compile_cache()

    g = from_spec(args.graph, scale=args.scale, degree=args.degree,
                  weighted=args.weighted, seed=args.seed)
    g, _ = g.remove_isolated()
    print(f"[bc] graph {g.name}: n={g.n} m={g.m}")

    if args.approx:
        return run_approx(args, g)

    query = _query_from_args(args, "exact")
    start_batch = 0
    lam_acc = np.zeros(g.n)
    if args.ckpt_dir:
        step = ckpt_lib.latest_step(args.ckpt_dir)
        if step is not None:
            flat, _ = ckpt_lib.restore(args.ckpt_dir)
            lam_acc = flat["lam"]
            start_batch = step + 1
            # The sweep's source ranges are keyed by nb: a resume must
            # reuse the checkpoint's batch size, not whatever the planner
            # (or a changed --nb) would pick today. Checkpoints predating
            # the 'nb' key were written with the old fixed default
            # (args.nb or 64), so that is the only safe legacy fallback.
            ckpt_nb = int(flat["nb"]) if "nb" in flat else (args.nb or 64)
            if args.nb and args.nb != ckpt_nb:
                raise SystemExit(f"--nb {args.nb} mismatches checkpoint "
                                 f"batch size nb={ckpt_nb}")
            query = dataclasses.replace(query, n_b=ckpt_nb)
            print(f"[bc] resuming at batch {start_batch} (nb={ckpt_nb})")

    pl = bc_plan(g, query, n_devices=1)  # exact CLI sweep is single-host
    print(f"[bc] {pl.summary()} execution={pl.execution.describe()}"
          + (" [calibrated]" if pl.regime.get("calibrated") else ""))
    nb = pl.n_b
    total_batches = -(-g.n // nb)

    def progress(b, n_batches, lam):
        gb = start_batch + b  # global batch index across resumes
        if args.ckpt_dir:
            # Cumulative λ at the global step: a second kill + resume
            # restores the whole prefix, not just this run's segment.
            ckpt_lib.save(args.ckpt_dir, gb,
                          {"lam": lam + lam_acc, "batch": gb, "nb": nb})
        print(f"[bc] batch {gb + 1}/{total_batches}")

    t0 = time.time()
    sources = np.arange(start_batch * nb, g.n, dtype=np.int32)
    out = bc_solve(g, query, plan=pl, sources=sources, progress_cb=progress)
    lam = out.lam + lam_acc
    dt = time.time() - t0
    # TEPS as the paper counts it: every edge is traversed once per source
    teps = g.m * g.n / dt
    print(f"[bc] done in {dt:.2f}s — {teps:,.0f} TEPS (model)")
    top = np.argsort(lam)[::-1][:5]
    print("[bc] top-5 central vertices:", list(zip(top.tolist(),
                                                   np.round(lam[top], 2))))
    if args.verify:
        ref = (khop_ref(g, hops=args.hops) if args.metric == "khop"
               else _REFS[args.metric](g))
        np.testing.assert_allclose(lam, ref, rtol=1e-4, atol=1e-6)
        print(f"[bc] verified against {args.metric} host oracle")
    return lam


if __name__ == "__main__":
    main()
