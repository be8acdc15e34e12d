"""Paper Table 3: critical-path communication (W bytes, S messages).

Compares MFBC's 3D decomposition (replication c = pod axis) against the
2D-only baseline (c = 1 — what a CombBLAS-style square-grid code does),
from two sources:

* the analytic §5.2/§5.3 model at Blue-Waters scale (4096 cores) for the
  paper's graphs (Orkut / LiveJournal / Patents sizes), and
* HLO-measured per-device collective bytes of the compiled distributed BC
  step on a fake-device mesh, which realizes the same ratio structurally.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

from repro.spgemm.cost_model import best_replication, w_mfbc

# (name, n, m, diameter) — Table 2 of the paper.
PAPER_GRAPHS = [
    ("orkut", 3_100_000, 117_000_000, 9),
    ("livejournal", 4_800_000, 70_000_000, 16),
    ("patents", 3_800_000, 16_500_000, 22),
]


def table3_model(p=4096, nb=512, word=8) -> List[Dict]:
    """One batch of ``nb`` sources (paper Table 3 setting).

    With the batch size fixed at 512, the useful replication is
    c = nb·n/m (Theorem 5.1's n_b = c·m/n inverted); the 2D baseline is
    c = 1. Per-batch bytes: 4·nb·n·word/√(pc) frontier movement +
    c·m·word/p adjacency replication (charged fully to this batch —
    conservative against MFBC).
    """
    rows = []
    for name, n, m, d in PAPER_GRAPHS:
        c3 = max(1, min(int(nb * n / m), p))

        def batch_bytes(c):
            front = 4.0 * nb * n * word / math.sqrt(p * c)
            adj = c * m * word / p
            return front + (adj if c > 1 else 0.0)

        def batch_msgs(c):
            return d * math.sqrt(p / c) * math.log2(p)

        w2, w3 = batch_bytes(1), batch_bytes(c3)
        rows.append({
            "graph": name, "n": n, "m": m, "d": d, "c_3d": c3,
            "W_2d_GB": w2 / 1e9, "W_3d_GB": w3 / 1e9,
            "S_2d": batch_msgs(1), "S_3d": batch_msgs(c3),
            "ratio_W": w2 / max(w3, 1e-9),
        })
    return rows


def model_mesh_bytes(n: int, nb: int, iters: int, axes: Dict[str, int],
                     word: int = 4) -> float:
    """§5.2 model: per-device collective bytes of one compiled batch step.

    The Theorem 5.1 realization on the (pod, data, model) mesh (see
    ``core.dist_bc``'s module docstring): each relaxation moves the
    pod-local dense state (nb/c rows × n vertices) three times —
    frontier all-gather, monoid reduce, product re-gather — at
    ``1/√(p/c)`` of its footprint per device. One batch runs the forward
    and backward sweeps, ``iters`` relaxations each. Monoid leaf counts
    and tie-mask doubling are deliberately *not* modeled — they are the
    constant factors the measured/model ratio gate absorbs; the
    shape-to-shape *scaling* is what the model pins down.
    """
    p = 1
    for s in axes.values():
        p *= s
    c = axes.get("pod", 1)
    per_iter = 3.0 * word * (nb / c) * n / max(math.sqrt(p / c), 1.0)
    return per_iter * 2 * iters


def measured_mesh_collectives(n: int, nb: int, iters: int,
                              axes: Dict[str, int],
                              block: int = 512) -> Dict:
    """HLO-measured per-device collective bytes of the distributed step.

    Compiles the real ``core.dist_bc`` batch step on a fake host mesh
    with *abstract* arguments — nothing is allocated, so this prices
    scale-18+ graphs whose dense adjacency could never materialize —
    and accounts the wire bytes of every collective in the compiled
    module via ``repro.roofline.hlo_parse`` (while-loop bodies scaled by
    the static trip count ``iters``). The caller must already be inside
    a process whose fake device count covers ``axes`` (bc_scaling spawns
    a subprocess with ``--xla_force_host_platform_device_count`` set
    before jax initializes).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.dist_bc import (BCMeshConfig, build_mfbc_step,
                                    input_shardings)
    from repro.roofline.hlo_parse import collective_bytes

    names = tuple(axes)
    shape = tuple(axes[a] for a in names)
    mesh = jax.make_mesh(shape, names)
    lcm = axes["data"] * axes["model"]
    n_pad = -(-n // lcm) * lcm
    chunk = axes.get("pod", 1) * axes["data"]
    nb_pad = -(-nb // chunk) * chunk
    cfg = BCMeshConfig(n=n_pad, nb=nb_pad, iters_bf=iters, iters_br=iters,
                       pod_axis="pod" if "pod" in axes else None,
                       block=block)
    step = build_mfbc_step(mesh, cfg)  # already jitted
    sh_a, sh_at, sh_src, sh_val = input_shardings(mesh, cfg)
    t0 = time.time()
    compiled = step.lower(
        jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32, sharding=sh_a),
        jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32, sharding=sh_at),
        jax.ShapeDtypeStruct((nb_pad,), jnp.int32, sharding=sh_src),
        jax.ShapeDtypeStruct((nb_pad,), jnp.bool_, sharding=sh_val),
    ).compile()
    coll = collective_bytes(compiled.as_text(), {"*": iters})
    return {
        "axes": dict(axes),
        "n": n, "n_pad": n_pad, "nb": nb_pad, "iters": iters,
        "seconds_compile": time.time() - t0,
        "wire_bytes": coll["wire_bytes"],
        "messages": coll["messages"],
        "by_kind": {k: v for k, v in coll.items()
                    if k.startswith("wire_")},
        "model_bytes": model_mesh_bytes(n_pad, nb_pad, iters, axes),
    }
