import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, record memory/cost/collective analysis.

The two lines above MUST stay first (before any other import): jax locks
the device count on first initialization, and the dry-run needs 512
placeholder CPU devices to build the production meshes. Smoke tests and
benchmarks must NOT import this module.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-27b \
      --shape train_4k --mesh multi --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
      # runs every cell in a fresh subprocess each (memory isolation),
      # skipping cells whose JSON is already present.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


def cell_filename(arch: str, shape: str, mesh_kind: str) -> str:
    return f"{arch}__{shape}__{mesh_kind}.json"


def run_one(arch_id: str, shape_id: str, mesh_kind: str, out_dir: str,
            policy_overrides=None) -> dict:
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.hlo_parse import collective_bytes
    from repro.sharding.rules import make_policy

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    spec = get_arch(arch_id)
    cell = spec.cells()[shape_id]
    policy = make_policy(mesh, seq_shard=(spec.family == "lm"),
                         overrides=policy_overrides)
    if spec.family == "lm":
        # Production program: scan-over-layers (this is what must compile
        # and what memory_analysis describes).
        bundle = spec.build(cell, policy)
    elif spec.family == "bc":
        bundle = spec.build(cell, policy, unroll=True)
    else:
        bundle = spec.build(cell, policy)

    def _compile(b):
        with jax.sharding.set_mesh(mesh):
            jitted = jax.jit(b.fn, donate_argnums=b.donate)
            lowered = jitted.lower(*b.abstract_args)
            return lowered.compile()

    compiled = _compile(bundle)
    t_lower = 0.0
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    trips = dict(bundle.trip_counts)
    trip_map = {"*": trips.get("while", 1)}
    coll = collective_bytes(compiled.as_text(), trip_map)

    if spec.family == "lm":
        # Calibration: per-layer exact cost from two tiny unrolled builds
        # (scan bodies are counted once by cost_analysis; the production
        # layer count is recovered as outside + L x body).
        L = spec.config().n_layers

        def measure(k):
            bk = spec.build(cell, policy, unroll=True, layers_override=k)
            ck = _compile(bk)
            cost_k = ck.cost_analysis()
            coll_k = collective_bytes(ck.as_text(), {})
            return (float(cost_k.get("flops", 0.0)),
                    float(cost_k.get("bytes accessed", 0.0)), coll_k)

        f1, b1, c1 = measure(1)
        f2, b2, c2 = measure(2)
        cost = dict(cost)
        cost["flops"] = f1 + (L - 1) * (f2 - f1)
        cost["bytes accessed"] = b1 + (L - 1) * (b2 - b1)
        coll = {k: c1.get(k, 0.0) + (L - 1) * (c2.get(k, 0.0) - c1.get(k, 0.0))
                for k in set(c1) | set(c2)}
        coll = {k: max(v, 0.0) for k, v in coll.items()}
        trip_map = {"calibrated": L}

    record = {
        "arch": arch_id,
        "shape": shape_id,
        "mesh": mesh_kind,
        "n_devices": int(n_dev),
        "ok": True,
        "seconds_lower": round(t_lower, 2),
        "seconds_compile": round(t_compile, 2),
        "model_flops": bundle.model_flops,
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", 0.0)),
        "trip_counts": trips,
        "collectives": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", 0),
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_filename(arch_id, shape_id, mesh_kind))
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"[dryrun] OK {arch_id} x {shape_id} x {mesh_kind}: "
          f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
          f"peak/dev {record['memory']['peak_bytes']/2**30:.2f} GiB "
          f"flops/dev {record['flops_per_device']:.3e}")
    return record


def run_all(out_dir: str, mesh_kinds, only=None, timeout=3000):
    """Each cell in a fresh subprocess (isolation + incremental caching)."""
    from repro.configs import all_cells

    cells = all_cells()
    failures = []
    for mesh_kind in mesh_kinds:
        for arch_id, shape_id in cells:
            if only and arch_id not in only:
                continue
            path = os.path.join(out_dir, cell_filename(arch_id, shape_id,
                                                       mesh_kind))
            if os.path.exists(path):
                print(f"[dryrun] cached {arch_id} x {shape_id} x {mesh_kind}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch_id, "--shape", shape_id,
                   "--mesh", mesh_kind, "--out", out_dir]
            print(f"[dryrun] spawn {' '.join(cmd[3:])}")
            # placeholder CPU devices only: never let a child take a chip
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout,
                               env={**os.environ, "JAX_PLATFORMS": "cpu"})
            sys.stdout.write(r.stdout[-2000:])
            if r.returncode != 0:
                failures.append((arch_id, shape_id, mesh_kind))
                err = {"arch": arch_id, "shape": shape_id, "mesh": mesh_kind,
                       "ok": False, "error": r.stderr[-4000:]}
                with open(path + ".fail", "w") as f:
                    json.dump(err, f, indent=1)
                print(f"[dryrun] FAIL {arch_id} x {shape_id} x {mesh_kind}\n"
                      + r.stderr[-1500:])
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="multi", choices=["single", "multi",
                                                        "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        failures = run_all(args.out, kinds, only=args.only)
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("[dryrun] all cells OK")
        return
    for k in kinds:
        run_one(args.arch, args.shape, k, args.out)


if __name__ == "__main__":
    main()
