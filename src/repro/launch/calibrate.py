"""Measure real batch-step times and fit the α-β cost constants.

The analytic regime model (``spgemm.autotune.choose_bc_regime``) prices
the TPU target from first-principles hardware constants; on the host a
run actually executes on it can be off by orders of magnitude (CPU CI:
predicted 0.059s vs measured ~4.1s per run). This module closes the
measurement loop the ISSUE's KADABRA citation demands — the sampling
layer's decisions only pay off when the per-step cost underneath them
is real:

1. build one executor per execution variant (dense / dense+Pallas-kernel
   / COO) on an R-MAT calibration graph, via the same
   ``BCPlanner`` → ``build_executor`` path production runs use;
2. time warm ``step`` calls at two batch sizes (best-of-``reps``, after
   a compile+warmup call);
3. fit ``t(n_b) = α + W(n_b)/rate`` per variant, where
   ``W(n_b) = 2·est_iters·relax_ops(backend, n, m, n_b)`` is the
   planner's *own* priced work for one batch (``BCPlanner._est_iters``,
   ``cost_model.relax_ops``) — deriving the rate through the planner's
   iteration heuristic makes the heuristic's error cancel when the plan
   multiplies it back in, so ``predicted_seconds`` tracks measured
   wall-clock on same-family graphs;
4. persist a ``spgemm.cost_model.Calibration`` to
   ``results/cost_calibration.json`` (``--out`` / ``save_calibration``),
   where ``load_calibration`` feeds it back to ``BCPlanner``,
   ``choose_bc_regime`` and ``choose_sample_batch`` — on the same
   device kind only: ``meta`` records the ``platform`` and
   ``device_kind`` that measured it.

``benchmarks/bc_approx.py`` self-calibrates with ``calibrate()`` on its
own benchmark graph before planning, so the recorded
``predicted_seconds`` vs measured comparison ``tools/check_bench.py``
gates on (≤ 2× drift) is an honest closed loop.

Usage::

    PYTHONPATH=src python -m repro.launch.calibrate \
        --scale 10 --avg-degree 16 --nb 16,64 --reps 2 \
        --out results/cost_calibration.json
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.spgemm.cost_model import (Calibration, StepRates, relax_ops,
                                     save_calibration, variant_key)

#: (backend, use_kernel) pairs calibrated by default.
DEFAULT_VARIANTS: Tuple[Tuple[str, bool], ...] = (
    ("dense", False), ("dense", True), ("coo", False), ("csr", False))


def _measure_step_seconds(g, backend: str, use_kernel: bool, nb: int,
                          reps: int) -> float:
    """Warm wall-clock seconds of one padded ``step`` call (best of reps)."""
    from repro.bc.config import ExecutionConfig
    from repro.bc.executor import build_executor
    from repro.bc.planner import BCPlanner
    from repro.bc.query import BCQuery

    q = BCQuery(mode="approx", n_b=nb,
                execution=ExecutionConfig(backend=backend,
                                          use_kernel=use_kernel,
                                          placement="single_host"))
    plan = BCPlanner(calibration=None).plan(g, q, n_devices=1)
    ex = build_executor(g, plan)
    rng = np.random.default_rng(0)
    src = rng.integers(0, g.n, size=nb).astype(np.int32)
    valid = np.ones(nb, bool)
    ex.step(src, valid)  # compile + warm the caches
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        ex.step(src, valid)
        best = min(best, time.perf_counter() - t0)
    return best


def _fit_rates(backend: str, n: int, m: int, est_iters: int,
               t_by_nb: Dict[int, float]) -> StepRates:
    """Fit (rate, overhead) from measured batch times at two sizes.

    Two points on ``t(n_b) = α + W(n_b)/rate``: the slope over the
    priced work gives the throughput, the intercept (clamped ≥ 0 — a
    negative intercept is measurement noise) the fixed per-call α.
    Degenerate measurements (non-increasing time) fall back to a pure
    throughput fit through the larger point.
    """
    (nb1, t1), (nb2, t2) = sorted(t_by_nb.items())[:2]
    # est_iters is forwarded so the CSR variant's occupancy-amortized
    # per-iteration work is priced with the same iteration heuristic at
    # fit and predict time (dense/COO ignore it).
    w1 = 2.0 * est_iters * relax_ops(backend, n, m, nb1,
                                     est_iters=est_iters)
    w2 = 2.0 * est_iters * relax_ops(backend, n, m, nb2,
                                     est_iters=est_iters)
    if t2 > t1 > 0 and w2 > w1:
        rate = (w2 - w1) / (t2 - t1)
        overhead = max(0.0, t1 - w1 / rate)
    else:
        rate = w2 / max(t2, 1e-9)
        overhead = 0.0
    return StepRates(ops_per_s=rate, overhead_s=overhead)


def calibrate(g, *, nb_pair: Tuple[int, int] = (16, 64), reps: int = 2,
              variants: Sequence[Tuple[str, bool]] = DEFAULT_VARIANTS,
              verbose: bool = False) -> Calibration:
    """Measure ``variants`` on graph ``g`` and fit a ``Calibration``."""
    import jax

    from repro.bc.planner import BCPlanner

    dev = jax.devices()[0]

    est_iters = BCPlanner._est_iters(g.n, weighted=bool(np.any(g.w != 1.0)),
                                     iters=0)
    rates: Dict[str, StepRates] = {}
    measured: Dict[str, Dict[int, float]] = {}
    for backend, use_kernel in variants:
        t_by_nb: Dict[int, float] = {}
        for nb in sorted(set(nb_pair)):
            t_by_nb[nb] = _measure_step_seconds(g, backend, use_kernel,
                                                nb, reps)
            if verbose:
                print(f"[calibrate] {variant_key(backend, use_kernel)} "
                      f"n_b={nb}: {t_by_nb[nb]:.4f}s")
        key = variant_key(backend, use_kernel)
        measured[key] = t_by_nb
        if len(t_by_nb) == 1:  # degenerate pair: pure throughput fit
            (nb,) = t_by_nb
            t_by_nb = {0: 0.0, nb: t_by_nb[nb]}
        rates[key] = _fit_rates(backend, g.n, g.m, est_iters, t_by_nb)
    return Calibration(
        rates=rates,
        meta={
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "graph": {"n": int(g.n), "m": int(g.m)},
            "n_b": sorted(set(nb_pair)),
            "est_iters": int(est_iters),
            "reps": int(reps),
            "measured_step_s": {k: {str(nb): t for nb, t in v.items()}
                                for k, v in measured.items()},
            "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime()),
        })


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=10,
                    help="R-MAT scale of the calibration graph")
    ap.add_argument("--avg-degree", type=int, default=16)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--nb", default="16,64",
                    help="comma-separated batch-size pair to fit over")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--skip-kernel", action="store_true",
                    help="skip the Pallas dense-kernel variant (slow in "
                         "interpret mode on CPU)")
    ap.add_argument("--out", default=None,
                    help="output path (default results/cost_calibration.json"
                         " or $REPRO_BC_CALIBRATION)")
    args = ap.parse_args(argv)

    from repro.graphs.generators import rmat
    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()

    g = rmat(args.scale, args.avg_degree, seed=args.seed)
    nb_pair = tuple(int(x) for x in args.nb.split(","))
    variants = [v for v in DEFAULT_VARIANTS
                if not (args.skip_kernel and v[1])]
    cal = calibrate(g, nb_pair=nb_pair, reps=args.reps, variants=variants,
                    verbose=True)
    path = save_calibration(cal, args.out)
    print(f"[calibrate] wrote {path}")
    for key, r in sorted(cal.rates.items()):
        print(f"[calibrate]   {key}: {r.ops_per_s:.3e} ops/s "
              f"(+{r.overhead_s * 1e3:.2f} ms/call)")
    print(f"[calibrate] kernel_pays={cal.kernel_pays()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
