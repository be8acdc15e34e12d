"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * fig1_strong_scaling_*   — measured TEPS (real execution, small graphs)
                              + modeled TEPS at pod scale
  * fig1c_weighted          — weighted-vs-unweighted slowdown
  * fig2_weak_scaling_*     — edge-weak vs vertex-weak efficiency trend
  * table3_comm_*           — critical-path W/S: 2D baseline vs 3D MFBC
  * sec52_spgemm_*          — decomposition autotuner picks per regime
  * kernel_*                — Pallas kernel microbenches (interpret mode)
  * approx_bc_*             — exact-vs-sampled BC (speedup, top-k precision)

Run: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import time

import numpy as np


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def bench_fig1_strong_scaling() -> None:
    from benchmarks.bc_scaling import (measured_strong_scaling,
                                       modeled_strong_scaling)

    m = measured_strong_scaling(scale=7, degree=8, nb=64)
    _row("fig1_strong_measured_rmat_s7_e8", m["seconds"] * 1e6,
         f"teps={m['teps']:.3e}")
    for r in modeled_strong_scaling():
        _row(f"fig1_strong_model_p{r['p']}", r["seconds"] * 1e6,
             f"teps={r['teps']:.3e};c={r['c']}")


def bench_fig1c_weighted() -> None:
    from benchmarks.bc_scaling import weighted_slowdown

    w = weighted_slowdown()
    _row("fig1c_weighted_slowdown", 0.0,
         f"slowdown={w['slowdown']:.2f};paper_claim~2x")


def bench_fig2_weak_scaling() -> None:
    from benchmarks.bc_scaling import modeled_weak_scaling

    for kind in ("edge", "vertex"):
        rows = modeled_weak_scaling(kind=kind)
        for r in rows:
            _row(f"fig2_{kind}_weak_p{r['p']}", r["seconds"] * 1e6,
                 f"eff={r['efficiency']:.3f};comm_frac={r['comm_frac']:.3f}")


def bench_table3_comm() -> None:
    from benchmarks.comm_cost import measured_bc_collectives, table3_model

    for r in table3_model():
        _row(f"table3_model_{r['graph']}", 0.0,
             f"W2d={r['W_2d_GB']:.2f}GB;W3d={r['W_3d_GB']:.2f}GB;"
             f"ratio={r['ratio_W']:.2f};c={r['c_3d']}")
    for r in measured_bc_collectives():
        _row(f"table3_hlo_{r['cell']}", 0.0,
             f"wire={r['wire_GB_per_dev']:.3f}GB/dev;"
             f"msgs={r['msgs_per_dev']:.0f}")


def bench_sec52_spgemm() -> None:
    from benchmarks.spgemm_variants import variant_table

    for r in variant_table():
        _row(f"sec52_autotune_{r['regime']}", 0.0,
             f"pick={r['best_variant']}@{r['best_axes']};"
             f"win_vs_2d={r['win_vs_2d']:.1f}x")


def bench_bc_approx() -> None:
    from benchmarks.bc_approx import bench_bc_approx as bench
    from benchmarks.bc_approx import bench_mesh_epochs

    r = bench(scale=8, nb=64)  # smoke-sized inside the CSV sweep
    _row(f"approx_{r['name']}", r["seconds_approx"] * 1e6,
         f"speedup={r['speedup']:.2f}x;topk_prec={r['topk_precision']:.2f};"
         f"spearman={r['spearman']:.3f};samples={r['n_samples']}")
    m = bench_mesh_epochs(scale=8, nb=64)
    _row("approx_mesh_epochs_s8", m["mesh"]["seconds"] * 1e6,
         f"epochs={m['mesh']['n_epochs']};samples={m['mesh']['n_samples']};"
         f"hoeffding={m['hoeffding_budget']};"
         f"saved={m['mesh']['samples_saved']}")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    nb, n = 128, 512
    fw = jnp.asarray(np.where(rng.random((nb, n)) < 0.5,
                              rng.integers(0, 20, (nb, n)), np.inf),
                     jnp.float32)
    fm = jnp.asarray((rng.random((nb, n)) < 0.5).astype(np.float32))
    a = jnp.asarray(np.where(rng.random((n, n)) < 0.3,
                             rng.integers(1, 9, (n, n)), np.inf), jnp.float32)
    f = jax.jit(lambda fw, fm, a: ops.multpath_matmul(fw, fm, a))
    f(fw, fm, a)[0].block_until_ready()
    t0 = time.time()
    reps = 3
    for _ in range(reps):
        f(fw, fm, a)[0].block_until_ready()
    us = (time.time() - t0) / reps * 1e6
    flops = 4 * nb * n * n
    _row("kernel_multpath_mm_512", us, f"interp_mode_gflops={flops/us/1e3:.2f}")


def main() -> None:
    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    bench_sec52_spgemm()
    bench_table3_comm()
    bench_fig2_weak_scaling()
    bench_fig1c_weighted()
    bench_fig1_strong_scaling()
    bench_bc_approx()
    bench_kernels()


if __name__ == "__main__":
    main()
