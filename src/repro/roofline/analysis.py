"""Roofline analysis over the dry-run records.

For each (arch × shape × mesh) cell:

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = wire_bytes_per_device / ICI_link_bw

Terms are *per step* wall-time lower bounds; the dominant term is the
bottleneck. ``MODEL_FLOPS / HLO_FLOPs`` measures how much compiled compute
is algorithmically useful (catches remat/dispatch waste). The estimated
step time assumes perfect compute/comm overlap (max of terms); the
"roofline fraction" = compute_term / max(terms) is the §Perf score.

Usage:
  PYTHONPATH=src python -m repro.roofline.analysis --dryrun results/dryrun \
      --out results/roofline.md
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

from repro.spgemm.cost_model import DEVICE_PEAKS, TARGET_KIND

PEAKS = DEVICE_PEAKS[TARGET_KIND]  # the dry-run cells model the v5e target


def _advice(rec: Dict, dominant: str) -> str:
    fam = rec["arch"].split("-")[0]
    if dominant == "collective":
        return ("shrink the gathered operand (2D->3D decomposition / more "
                "replication c, or keep weights resident)" if fam in
                ("mfbc_paper",) else
                "overlap or shrink DP/FSDP gathers (bigger per-device batch, "
                "int8/topk grad compression, expert-local all-to-all)")
    if dominant == "memory":
        return ("bf16/int8 the dominant resident tensor (KV cache / "
                "embedding rows / frontier pairs) or fuse the streaming op")
    return "compute-bound: raise MXU occupancy (bf16, larger tiles)"


def _bc_kernel_terms(rec: Dict) -> Dict:
    """mfbc_paper cells: production terms from the Pallas kernel tile model
    (512-cube tiles; accumulators resident in VMEM — see tropical_mm.py).
    The HLO terms describe the pure-jnp fallback, which materializes the
    candidate blocks in HBM (~10^3x more traffic)."""
    meta = {"bc_web_256k": (262144, 8192, 8), "bc_dense_64k": (65536, 16384, 6)}
    n, nb, iters = meta[rec["shape"]]
    pod = 2 if rec["mesh"] == "multi" else 1
    nb_loc, n_loc = nb // pod, n // 16
    relaxes = 2 * (iters + 1) + 1
    bm = bk = bn = 512
    f = nb_loc * n_loc * 8 * (n_loc // bn)
    a = n_loc * n_loc * 4 * (nb_loc // bm)
    cbytes = nb_loc * n_loc * 8
    ops = 4.0 * nb_loc * n_loc * n_loc
    return {"t_memory_s": (f + a + cbytes) * relaxes / PEAKS.hbm_bw,
            "t_compute_s": ops * relaxes / PEAKS.vpu_ops}


def analyze_record(rec: Dict, *, peak_flops: float = PEAKS.bf16_flops
                   ) -> Dict:
    flops_dev = rec["flops_per_device"]
    bytes_dev = rec["bytes_accessed_per_device"]
    wire = rec["collectives"].get("wire_bytes", 0.0)
    operand = rec["collectives"].get("operand_bytes", 0.0)
    t_compute = flops_dev / peak_flops
    t_memory = bytes_dev / PEAKS.hbm_bw
    t_coll = wire / PEAKS.ici_bw
    if rec["arch"] == "mfbc_paper":
        kt = _bc_kernel_terms(rec)
        t_compute = kt["t_compute_s"]
        t_memory = kt["t_memory_s"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    t_step = max(terms.values())
    model = rec.get("model_flops", 0.0)
    total_hlo = flops_dev * rec["n_devices"]
    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh", "n_devices")},
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "t_step_s": t_step,
        "roofline_fraction": (t_compute / t_step) if t_step > 0 else 0.0,
        "model_flops": model,
        "hlo_flops_total": total_hlo,
        "useful_flops_ratio": model / total_hlo if total_hlo else 0.0,
        "collective_wire_bytes": wire,
        "collective_operand_bytes": operand,
        "peak_mem_gib": rec["memory"]["peak_bytes"] / 2 ** 30,
        "arg_mem_gib": rec["memory"]["argument_bytes"] / 2 ** 30,
        "advice": _advice(rec, dominant),
    }


def load_all(dryrun_dir: str) -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("ok"):
            out.append(rec)
    return out


def _fmt_t(x: float) -> str:
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.1f}ms"
    return f"{x * 1e6:.0f}us"


def to_markdown(rows: List[Dict], mesh: Optional[str] = None) -> str:
    hdr = ("| arch | shape | mesh | compute | memory | collective | bound | "
           "roofline frac | useful/HLO | mem/dev |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        if mesh and r["mesh"] != mesh:
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {_fmt_t(r['t_compute_s'])} | {_fmt_t(r['t_memory_s'])} "
            f"| {_fmt_t(r['t_collective_s'])} | **{r['dominant']}** "
            f"| {r['roofline_fraction']:.2f} "
            f"| {r['useful_flops_ratio']:.2f} "
            f"| {r['arg_mem_gib'] + r['peak_mem_gib']:.1f} GiB |")
    return hdr + "\n".join(lines) + "\n"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun")
    ap.add_argument("--out", default="results/roofline.md")
    ap.add_argument("--json-out", default="results/roofline.json")
    args = ap.parse_args(argv)

    recs = load_all(args.dryrun)
    rows = [analyze_record(r) for r in recs]
    rows.sort(key=lambda r: (r["mesh"], r["arch"], r["shape"]))
    md = ["# Roofline (single-pod 16x16 = 256 chips)\n",
          to_markdown(rows, "single"),
          "\n# Multi-pod (2x16x16 = 512 chips) dry-run terms\n",
          to_markdown(rows, "multi")]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write("".join(md))
    with open(args.json_out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"[roofline] wrote {args.out} ({len(rows)} cells)")
    # worst cells (hillclimb candidates)
    single = [r for r in rows if r["mesh"] == "single"]
    if single:
        worst = sorted(single, key=lambda r: r["roofline_fraction"])[:5]
        print("[roofline] worst roofline fractions:")
        for r in worst:
            print(f"  {r['arch']} x {r['shape']}: "
                  f"{r['roofline_fraction']:.2f} ({r['dominant']})")
    return rows


if __name__ == "__main__":
    main()
