"""Perf hillclimbing driver (EXPERIMENTS.md §Perf).

Three cells, chosen per the methodology (worst roofline fraction, most
collective-bound, most paper-representative):

  gcn2d   — gcn-cora x ogb_products: replace the GSPMD 1D-variant-C
            allreduce with the paper's 2D edge partition (shard_map).
  qwen3ep — qwen3 x train_4k (multi): shard experts over (pod, model)
            — EP degree 32 halves the per-device FSDP gather bytes.
  bcblock — mfbc_paper x bc_web_256k: relax block-size sweep (measured)
            + Pallas kernel tile-traffic model (the TPU target numbers).

Each writes results/perf_iters/<name>.json with before/after terms.

Usage: PYTHONPATH=src python -m repro.launch.perf_hillclimb --which all

The 512-device fake topology is forced in ``main()`` (it must run
before jax initializes); importing this module for its measurement
scaffolding (``_compile_stats``, the sweeps) does NOT touch the device
count — ``repro.launch.calibrate`` reuses the helpers in-process.
"""
import argparse
import json
import os
import time


def _write(name, record):
    os.makedirs("results/perf_iters", exist_ok=True)
    with open(f"results/perf_iters/{name}.json", "w") as f:
        json.dump(record, f, indent=1)
    print(f"[perf] wrote results/perf_iters/{name}.json")


def _compile_stats(fn, args, donate=()):
    import jax

    from repro.roofline.hlo_parse import collective_bytes

    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", 0.0)),
        "wire_bytes": coll["wire_bytes"],
        "messages": coll["messages"],
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
    }


def hillclimb_gcn2d():
    """ogb_products on the multi-pod mesh: baseline vs 2D edge partition."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_production_mesh
    from repro.models.gnn_dist import abstract_inputs, build_gcn2d_loss, \
        make_grid

    mesh = make_production_mesh(multi_pod=True)
    n, e, d_in, dh, classes = 2449029, 61859140, 100, 16, 47
    grid = make_grid(mesh, n, e)
    loss2d = build_gcn2d_loss(mesh, grid, n_layers=2)
    params = {"w": [jax.ShapeDtypeStruct((d_in, dh), jnp.float32),
                    jax.ShapeDtypeStruct((dh, classes), jnp.float32)]}
    ab = abstract_inputs(mesh, grid, d_in)
    args = (params, ab["x"], ab["src"], ab["dst"], ab["coef"],
            ab["labels"], ab["mask"])

    with jax.sharding.set_mesh(mesh):
        after = _compile_stats(jax.grad(loss2d), args)

    baseline_path = "results/dryrun/gcn-cora__ogb_products__multi.json"
    before = json.load(open(baseline_path))
    rec = {
        "cell": "gcn-cora x ogb_products x multi",
        "hypothesis": ("GSPMD lowers segment_sum message passing as the "
                       "paper's 1D variant C (full-size partial + "
                       "all-reduce, ~2|H| bytes/dev/layer); the 2D edge "
                       "partition should cut collectives ~R*C*2/(R+C)=21x "
                       "(R=32, C=16)"),
        "before_wire_bytes": before["collectives"]["wire_bytes"],
        "after_wire_bytes": after["wire_bytes"],
        "win": before["collectives"]["wire_bytes"]
        / max(after["wire_bytes"], 1.0),
        "before": {k: before.get(k) for k in
                   ("flops_per_device", "bytes_accessed_per_device")},
        "after": after,
        "note": ("before = full train step (loss+grad+adamw); after = "
                 "loss+grad (optimizer params replicated+tiny). Grad "
                 "psum of the replicated weights over 512 devices is "
                 "included in 'after'."),
    }
    _write("gcn2d", rec)
    return rec


def hillclimb_qwen3_ep():
    """qwen3 train_4k multi: experts over (pod, model) (EP degree 32)."""
    from repro.launch.dryrun import run_one

    rec_after = run_one("qwen3-moe-235b-a22b", "train_4k", "multi",
                        "results/perf_iters/qwen3ep_raw",
                        policy_overrides={"expert": ("pod", "model"),
                                          "fsdp": ("data",)})
    before = json.load(open(
        "results/dryrun/qwen3-moe-235b-a22b__train_4k__multi.json"))
    rec = {
        "cell": "qwen3-moe x train_4k x multi",
        "hypothesis": ("FSDP gathers of expert weights dominate the wire "
                       "(302MB/layer/dev at EP=16); sharding experts over "
                       "(pod, model) doubles EP to 32 and should halve "
                       "per-device gathered expert bytes"),
        "before_wire_bytes": before["collectives"]["wire_bytes"],
        "after_wire_bytes": rec_after["collectives"]["wire_bytes"],
        "win": before["collectives"]["wire_bytes"]
        / max(rec_after["collectives"]["wire_bytes"], 1.0),
        "before_mem": before["memory"],
        "after_mem": rec_after["memory"],
    }
    _write("qwen3ep", rec)
    return rec


def hillclimb_bc_blocks():
    """mfbc_paper bc_web_256k: measured block sweep + kernel tile model."""
    import jax

    from repro.configs import get_arch
    from repro.core import dist_bc
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.hlo_parse import collective_bytes
    from repro.spgemm.cost_model import DEVICE_PEAKS, TARGET_KIND

    peaks = DEVICE_PEAKS[TARGET_KIND]

    mesh = make_production_mesh(multi_pod=True)
    n, nb, iters = 262144, 8192, 8

    def measure(block):
        cfg = dist_bc.BCMeshConfig(n=n, nb=nb, iters_bf=iters,
                                   iters_br=iters, pod_axis="pod",
                                   use_kernel=False, block=block,
                                   unroll=True)
        step = dist_bc.build_mfbc_step(mesh, cfg)
        sh = dist_bc.input_shardings(mesh, cfg)
        import jax.numpy as jnp
        sds = jax.ShapeDtypeStruct
        args = (sds((n, n), jnp.float32, sharding=sh[0]),
                sds((n, n), jnp.float32, sharding=sh[1]),
                sds((nb,), jnp.int32, sharding=sh[2]),
                sds((nb,), jnp.bool_, sharding=sh[3]))
        with jax.sharding.set_mesh(mesh):
            compiled = jax.jit(step).lower(*args).compile()
        cost = compiled.cost_analysis()
        return {"block": block,
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "flops": float(cost.get("flops", 0.0)),
                "wire_bytes": collective_bytes(compiled.as_text())
                ["wire_bytes"]}

    sweep = [measure(b) for b in (256, 1024, 4096)]

    # Pallas kernel tile-traffic model (TPU target; kernels validated for
    # correctness in interpret mode, perf from first principles):
    # per relax per device, tiles (bm, bk, bn):
    #   F bytes = nb_loc*n_loc*8 * (n_loc/bn)   [two f32 arrays: w, m]
    #   A bytes = n_loc*n_loc*4 * (nb_loc/bm)
    #   C bytes = nb_loc*n_loc*8 (written once; accumulators live in VMEM)
    nb_loc, n_loc = nb // 2, n // 16  # (pod, data) rows; model cols
    relaxes = 2 * (iters + 1) + 1

    def kernel_model(bm, bk, bn):
        f = nb_loc * n_loc * 8 * (n // 16 // bn)
        a = (n // 16) * (n // 16) * 4 * (nb_loc // bm)
        c = nb_loc * n_loc * 8
        vmem = (bm * bk * 2 + bk * bn + bm * bn * 2) * 4
        ops = 4.0 * nb_loc * (n // 16) * (n // 16)  # min-plus+tie updates
        return {"tiles": (bm, bk, bn),
                "hbm_bytes_per_relax": f + a + c,
                "hbm_bytes_total": (f + a + c) * relaxes,
                "t_memory_s": (f + a + c) * relaxes / peaks.hbm_bw,
                "t_compute_s": ops * relaxes / peaks.vpu_ops,
                "vmem_bytes": vmem}

    kmodel = [kernel_model(*t) for t in
              ((128, 128, 128), (256, 256, 256), (512, 512, 512),
               (512, 1024, 512))]

    rec = {
        "cell": "mfbc_paper x bc_web_256k x multi",
        "hypothesis": ("the jnp fallback relax materializes candidate "
                       "blocks in HBM; block size trades candidate-buffer "
                       "traffic vs accumulator round trips. On the TPU "
                       "target the Pallas kernel keeps both accumulators "
                       "in VMEM: traffic = F*(n/bn) + A*(nb/bm) + C; "
                       "512-tiles should drop the memory term ~100x vs "
                       "the fallback and make the cell VPU-compute-bound"),
        "measured_block_sweep": sweep,
        "kernel_tile_model": kmodel,
        "hw": {"hbm_bw": peaks.hbm_bw, "vpu_ops": peaks.vpu_ops},
    }
    _write("bcblock", rec)
    return rec


def main():
    # Must precede jax initialization; kept out of module scope so
    # importing the scaffolding never mutates the process's devices.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", default="all",
                    choices=["all", "gcn2d", "qwen3ep", "bcblock"])
    args = ap.parse_args()
    if args.which in ("all", "gcn2d"):
        hillclimb_gcn2d()
    if args.which in ("all", "qwen3ep"):
        hillclimb_qwen3_ep()
    if args.which in ("all", "bcblock"):
        hillclimb_bc_blocks()


if __name__ == "__main__":
    main()
