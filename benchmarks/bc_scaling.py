"""Scaling benchmarks: paper Figures 1 & 2 plus the CI-tracked record.

Three layers of evidence on a CPU-only container:

* measured (small) — real single-host executions of the batched MFBC
  step over R-MAT graphs, reported as TEPS (``measured_strong_scaling``;
  the paper's metric: m·n_sources / seconds);
* modeled — the Theorem 5.1 α–β cost evaluated at Blue-Waters-like and
  v5e-pod scales, reproducing the shapes of Fig. 1 (strong scaling) and
  Fig. 2 (edge-weak vs vertex-weak);
* measured (large) — the ``scaling`` record: R-MAT scale 18/20 and a
  generated stand-in for the SNAP ego-Facebook graph, ingested out-of-core
  through
  ``repro.graphs.formats.load_graph`` (chunked, digest-verified), run
  through the calibrated COO fast path for sources/sec, plus
  HLO-*measured* per-device collective bytes of the compiled distributed
  step at ≥ 2 mesh shapes against the §5.2 model prediction
  (``benchmarks.comm_cost.measured_mesh_collectives``). The record lands
  in ``BENCH_scaling.json`` — or is merged into ``BENCH_approx.json``
  under the ``"scaling"`` key with ``--merge`` — and is gated by
  ``tools/check_bench.py`` (bytes ratio vs model within tolerance, mesh
  -shape reduction matching the model, no sources/sec regression vs
  ``benchmarks/baselines/scaling.json``).

  PYTHONPATH=src python -m benchmarks.bc_scaling                # full
  PYTHONPATH=src python -m benchmarks.bc_scaling --smoke \
      --merge BENCH_approx.json                                 # CI leg

The collective measurement needs 64 fake host devices, which must be
configured before jax initializes — ``main`` re-invokes itself in a
``--comm-only`` subprocess for that step, so the measured sources/sec
legs in the parent keep the real (single-device) topology. That child
only compiles, on the CPU (``JAX_PLATFORMS=cpu``): the parent holds the
accelerator, and a second process must never try to take it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# NOTE: all repro imports in this module are lazy — ``--comm-only`` must
# set XLA_FLAGS before anything initializes jax (repro.spgemm's package
# __init__ pulls it in via the autotuner).

DATASET_DIR = "results/datasets"
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baselines", "scaling.json")
# The two Table-3 mesh cells at p = 64: the 2D square grid (c = 1, what a
# CombBLAS-style code does) vs the 3D replicated grid (c = 4) — the §5.2
# claim is the bytes ratio between exactly these two.
COMM_SHAPES: Dict[str, Dict[str, int]] = {
    "8x8": {"data": 8, "model": 8},
    "4x4x4": {"pod": 4, "data": 4, "model": 4},
}


# --------------------------------------------------------------------------
# Paper Figures 1 & 2 (benchmarks.run CSV rows).
# --------------------------------------------------------------------------


def measured_strong_scaling(scale=7, degree=8, nb=64, weighted=False,
                            repeats=1) -> Dict:
    from repro.bc import BCQuery, ExecutionConfig, solve
    from repro.bc import plan as bc_plan
    from repro.graphs.generators import rmat

    g = rmat(scale, degree, weighted=weighted, seed=3)
    g, _ = g.remove_isolated()
    q = BCQuery(mode="exact", n_b=nb,
                execution=ExecutionConfig(backend="dense"))
    pl = bc_plan(g, q, n_devices=1)
    solve(g, q, plan=pl)  # warm up (jit compile)
    t0 = time.time()
    lam = solve(g, q, plan=pl).lam
    dt = time.time() - t0
    teps = g.m * g.n / dt
    return {"n": g.n, "m": g.m, "seconds": dt, "teps": teps,
            "weighted": weighted, "lam_sum": float(lam.sum())}


def modeled_strong_scaling(n=1 << 22, k=64, d=8, mem=16 * 2 ** 30,
                           ps=(64, 256, 1024, 4096)) -> List[Dict]:
    from repro.spgemm.cost_model import best_replication, w_mfbc

    m = n * k
    rows = []
    for p in ps:
        c = best_replication(n, m, p, mem, d=d)
        r = w_mfbc(n, m, p, c, d)
        rows.append({"p": p, "c": c, "seconds": r["seconds"],
                     "teps": m * n / r["seconds"],
                     "bytes": r["beta_bytes"], "msgs": r["alpha_msgs"]})
    return rows


def modeled_weak_scaling(kind="edge", base_n=1 << 18, base_p=64, d=8,
                         mem=16 * 2 ** 30, steps=4) -> List[Dict]:
    """edge: m/p and m/n^2 fixed (n ~ sqrt(p)); vertex: n/p and k fixed."""
    from repro.spgemm.cost_model import best_replication, w_mfbc

    rows = []
    for i in range(steps):
        p = base_p * 4 ** i
        if kind == "edge":
            n = int(base_n * 2 ** i)  # n^2/p fixed
            k = n / 64
        else:
            n = base_n * 4 ** i  # n/p fixed
            k = 64
        m = int(n * k)
        c = best_replication(n, m, p, mem, d=d)
        r = w_mfbc(n, m, p, c, d)
        # efficiency = useful-compute fraction of the (overlapped) step:
        # drops exactly when communication outgrows the per-node work —
        # the paper's vertex-weak deterioration.
        eff = r["compute_seconds"] / max(r["seconds"], 1e-30)
        rows.append({"p": p, "n": n, "m": m, "c": c,
                     "seconds": r["seconds"], "efficiency": eff,
                     "comm_frac": r["comm_seconds"]
                     / (r["comm_seconds"] + r["compute_seconds"])})
    return rows


def weighted_slowdown(scale=6, degree=6, nb=32) -> Dict:
    """Fig. 1(c): weighted graphs roughly double the relax count."""
    u = measured_strong_scaling(scale, degree, nb, weighted=False)
    w = measured_strong_scaling(scale, degree, nb, weighted=True)
    return {"teps_unweighted": u["teps"], "teps_weighted": w["teps"],
            "slowdown": u["teps"] / max(w["teps"], 1e-9)}


# --------------------------------------------------------------------------
# Out-of-core datasets: cached R-MAT RCOO files + a SNAP-like text graph.
# --------------------------------------------------------------------------


def rmat_dataset(scale: int, degree: int = 8, seed: int = 7,
                 cache_dir: str = DATASET_DIR) -> str:
    """Write (once) the raw scale-``scale`` R-MAT arc stream as RCOO.gz.

    The generator runs in memory — arcs are just arrays — but the
    *benchmark* then forgets the arrays and goes through the on-disk
    chunked ingest, which is the code path under test.
    """
    from repro.graphs.formats import write_binary_coo
    from repro.graphs.generators import rmat

    path = os.path.join(cache_dir, f"rmat_s{scale}_e{degree}_{seed}.rcoo.gz")
    if not os.path.exists(path):
        os.makedirs(cache_dir, exist_ok=True)
        g = rmat(scale, degree, seed=seed)
        write_binary_coo(path, g)
    return path


def snap_like_graph(cache_dir: str = DATASET_DIR) -> str:
    """Write (once) a generated stand-in for SNAP ego-Facebook as SNAP text.

    The same shape class (undirected power-law, n ≈ 4k), generated from a
    fixed seed, so the text-ingest leg needs no download.
    """
    synth = os.path.join(cache_dir, "facebook_synth.txt.gz")
    if not os.path.exists(synth):
        from repro.graphs.formats import write_edge_list
        from repro.graphs.generators import rmat

        os.makedirs(cache_dir, exist_ok=True)
        g = rmat(12, 22, seed=41)  # ~4k vertices, ~88k arcs: SNAP-like
        write_edge_list(path=synth, g=g, weights=False)
    return synth


def ingest_leg(path: str, *, symmetrize: bool = False,
               chunk_edges: int = 1 << 18, name: Optional[str] = None
               ) -> Tuple["object", Dict]:
    """Chunked on-disk ingest, timed. Returns (IngestResult, record)."""
    from repro.graphs.formats import load_graph

    t0 = time.time()
    res = load_graph(path, chunk_edges=chunk_edges, symmetrize=symmetrize,
                     remove_isolated=True, name=name)
    dt = time.time() - t0
    rec = {
        "graph": res.graph.name,
        "path": path,
        "n": res.graph.n,
        "m": res.graph.m,
        "edges_read": res.edges_read,
        "n_chunks": res.n_chunks,
        "chunk_edges": chunk_edges,
        "seconds": dt,
        "edges_per_sec": res.edges_read / max(dt, 1e-9),
        "digest": res.digest,
    }
    return res, rec


# --------------------------------------------------------------------------
# Measured sources/sec legs (single-host COO fast path).
# --------------------------------------------------------------------------


def measured_bc_leg(ingest, *, nb: int = 16, iters: int = 48,
                    batches: int = 2, backend: str = "coo",
                    seed: int = 0, baselines: Optional[Dict] = None) -> Dict:
    """Steady-state sources/sec of the sampled BC sweep on one ingest.

    Plans from the ingest's ``GraphStats`` (no edge arrays needed at
    plan time — the out-of-core planning contract), then executes a
    fixed ``batches·nb`` uniform sample budget on the pinned backend
    after a one-batch jit warm-up.
    """
    from repro.bc import BCQuery, ExecutionConfig, solve
    from repro.bc import plan as bc_plan

    g = ingest.graph
    q = BCQuery(mode="approx", eps=0.1, delta=0.1, n_b=nb, iters=iters,
                strategy="uniform", max_samples=batches * nb, seed=seed,
                execution=ExecutionConfig(backend=backend))
    pl = bc_plan(ingest.stats, q, n_devices=1)  # plan without the arrays
    solve(g, dataclasses.replace(q, max_samples=nb, seed=seed + 1), plan=pl)
    t0 = time.time()
    out = solve(g, q, plan=pl)
    dt = time.time() - t0
    rec = {
        "graph": g.name,
        "n": g.n,
        "m": g.m,
        "nb": nb,
        "iters": iters,
        "backend": backend,
        "digest": ingest.digest,
        "n_sources": out.approx.n_samples,
        "seconds": dt,
        "sources_per_sec": out.approx.n_samples / max(dt, 1e-9),
        "plan": out.plan.to_json(),
    }
    base = (baselines or {}).get(g.name, {}).get("sources_per_sec")
    if base:
        rec["baseline_sources_per_sec"] = base
    return rec


# --------------------------------------------------------------------------
# HLO-measured collective bytes vs the §5.2 model (fake-mesh subprocess).
# --------------------------------------------------------------------------


def comm_record(scale: int, nb: int = 64, iters: int = 40,
                shapes: Dict[str, Dict[str, int]] = None) -> Dict:
    """Per-shape measured-vs-model collective bytes (call with the fake
    devices already configured — ``main --comm-only`` does)."""
    from benchmarks.comm_cost import measured_mesh_collectives

    shapes = shapes or COMM_SHAPES
    per_shape = {}
    for tag, axes in shapes.items():
        r = measured_mesh_collectives(1 << scale, nb, iters, axes)
        r["ratio"] = r["wire_bytes"] / max(r["model_bytes"], 1e-9)
        per_shape[tag] = r
    rec = {"scale": scale, "nb": nb, "iters": iters, "shapes": per_shape}
    tags = list(per_shape)
    if len(tags) >= 2:
        hi = max(tags, key=lambda t: per_shape[t]["model_bytes"])
        lo = min(tags, key=lambda t: per_shape[t]["model_bytes"])
        rec["reduction_measured"] = (per_shape[hi]["wire_bytes"]
                                     / max(per_shape[lo]["wire_bytes"], 1e-9))
        rec["reduction_model"] = (per_shape[hi]["model_bytes"]
                                  / max(per_shape[lo]["model_bytes"], 1e-9))
    return rec


def comm_record_subprocess(scale: int, nb: int = 64, iters: int = 40,
                           timeout: float = 1200.0) -> Dict:
    """Run ``comm_record`` in a fresh CPU-only process with 64 fake devices.

    The parent's jax is already initialized on the real topology (and
    holds the accelerator); forcing fake devices there would poison the
    measured legs' timings and the planner's routing, so the comm
    measurement re-invokes this module with ``--comm-only`` under
    ``JAX_PLATFORMS=cpu``.
    """
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out = f.name
    try:
        cmd = [sys.executable, "-m", "benchmarks.bc_scaling", "--comm-only",
               "--scale", str(scale), "--nb", str(nb),
               "--iters", str(iters), "--out", out]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout,
                           env={**os.environ, "JAX_PLATFORMS": "cpu"},
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        if r.returncode != 0:
            raise RuntimeError(f"comm subprocess failed:\n{r.stderr[-2000:]}")
        with open(out) as f:
            return json.load(f)
    finally:
        os.unlink(out)


def _comm_only_main(args) -> None:
    # XLA_FLAGS was set by main() before anything imported jax.
    rec = comm_record(args.scale, nb=args.nb, iters=args.iters)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)


# --------------------------------------------------------------------------
# The full scaling record.
# --------------------------------------------------------------------------


def load_baselines(path: str = BASELINE_PATH) -> Dict:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def bench_scaling(smoke: bool = False, budget_s: float = 0.0,
                  comm_scale: int = 18, comm_nb: int = 64,
                  comm_iters: int = 40) -> Dict:
    """Assemble the ``scaling`` record (see module docstring)."""
    t_start = time.time()
    baselines = load_baselines()
    ingests: List[Dict] = []
    legs: List[Dict] = []

    def over_budget() -> bool:
        return bool(budget_s) and (time.time() - t_start) > budget_s

    # -- SNAP-like text graph (small, runs everywhere) ------------------
    res, irec = ingest_leg(snap_like_graph(), symmetrize=True,
                           chunk_edges=1 << 15)
    ingests.append(irec)
    legs.append(measured_bc_leg(res, nb=32, iters=24, batches=2,
                                baselines=baselines))

    # -- R-MAT scale 18 (the CI-gated big leg) --------------------------
    res, irec = ingest_leg(rmat_dataset(18), name="rmat_s18")
    ingests.append(irec)
    legs.append(measured_bc_leg(res, nb=16, iters=48, batches=2,
                                baselines=baselines))

    # -- R-MAT scale 20 (full runs only; budget-guarded) ----------------
    skipped = []
    if smoke or over_budget():
        skipped.append({"graph": "rmat_s20",
                        "reason": "smoke" if smoke else "budget"})
    else:
        res, irec = ingest_leg(rmat_dataset(20), name="rmat_s20")
        ingests.append(irec)
        legs.append(measured_bc_leg(res, nb=16, iters=56, batches=1,
                                    baselines=baselines))

    # -- HLO-measured collective bytes vs §5.2 model --------------------
    comm = comm_record_subprocess(comm_scale, nb=comm_nb, iters=comm_iters)

    return {
        "smoke": smoke,
        "ingest": ingests,
        "legs": legs,
        "skipped": skipped,
        "comm": comm,
        "baseline_path": os.path.relpath(BASELINE_PATH),
        "seconds_total": time.time() - t_start,
    }


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: skip the scale-20 leg")
    ap.add_argument("--budget-s", type=float, default=0.0,
                    help="soft wall-clock budget; optional legs are "
                         "skipped once exceeded")
    ap.add_argument("--out", default="BENCH_scaling.json")
    ap.add_argument("--merge", default=None, metavar="BENCH_APPROX",
                    help="also merge the record into this BENCH_approx"
                         ".json under the 'scaling' key")
    ap.add_argument("--comm-only", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--scale", type=int, default=18)
    ap.add_argument("--nb", type=int, default=64)
    ap.add_argument("--iters", type=int, default=40)
    args = ap.parse_args(argv)

    if args.comm_only:
        if "jax" in sys.modules:
            raise SystemExit("--comm-only must run before jax initializes")
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise SystemExit("--comm-only compiles on fake CPU devices: "
                             "run it with JAX_PLATFORMS=cpu")
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=64 "
            + os.environ.get("XLA_FLAGS", ""))
        _comm_only_main(args)
        return {}

    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()

    rec = bench_scaling(smoke=args.smoke, budget_s=args.budget_s,
                        comm_scale=args.scale, comm_nb=args.nb,
                        comm_iters=args.iters)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    if args.merge:
        with open(args.merge) as f:
            approx = json.load(f)
        approx["scaling"] = rec
        with open(args.merge, "w") as f:
            json.dump(approx, f, indent=1)

    for i in rec["ingest"]:
        print(f"[bc_scaling] ingest {i['graph']}: {i['edges_read']} arcs "
              f"-> n={i['n']} m={i['m']} in {i['seconds']:.1f}s "
              f"({i['edges_per_sec']:.0f} arcs/s, {i['n_chunks']} chunks)")
    for leg in rec["legs"]:
        base = leg.get("baseline_sources_per_sec")
        extra = f" (baseline {base:.2f})" if base else ""
        print(f"[bc_scaling] {leg['graph']}: {leg['n_sources']} sources in "
              f"{leg['seconds']:.1f}s = {leg['sources_per_sec']:.2f} "
              f"sources/s on {leg['backend']}{extra}")
    comm = rec["comm"]
    for tag, r in comm["shapes"].items():
        print(f"[bc_scaling] comm {tag}: measured "
              f"{r['wire_bytes'] / 1e9:.2f} GB/dev vs model "
              f"{r['model_bytes'] / 1e9:.2f} GB (ratio {r['ratio']:.2f}, "
              f"compile {r['seconds_compile']:.1f}s)")
    if "reduction_measured" in comm:
        print(f"[bc_scaling] 2D->3D bytes reduction: measured "
              f"{comm['reduction_measured']:.2f}x vs model "
              f"{comm['reduction_model']:.2f}x")
    print(f"[bc_scaling] wrote {args.out}"
          + (f" and merged into {args.merge}" if args.merge else ""))
    return rec


if __name__ == "__main__":
    main()
