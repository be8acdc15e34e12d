"""2D edge-partitioned GNN message passing — the paper's SpGEMM insight
applied to graph neural networks (hillclimb, EXPERIMENTS.md §Perf).

Baseline GSPMD lowering of ``segment_sum`` message passing realizes the
paper's **1D variant C**: every device computes a full-size partial node
buffer and all-reduces it (bytes ≈ 2·|H| per layer per device). The 2D
decomposition (paper §5.2) assigns edges to a (R × C) = (data × model)
grid by (dst-range, src-shard):

* device (r, c) holds the edges whose **source** lives in its local
  feature shard S_c and whose **destination** falls in contiguous range r
  → message gather is 100% local;
* partial destination sums (N/R, h) reduce-scatter over ``model`` and
  all-gather over ``data`` — bytes ≈ |H|/R + |H|/C per device: a
  ``R·C·2/(R+C)`` ≈ 16x collective reduction on the production mesh.

Node state lives in the same interleaved Π-layout as the distributed BC
step (see ``repro.core.dist_bc`` module docstring); the closed-form id map
lets the host bucket edges once. Implemented for GCN (the regime
representative); the same structure drops into GIN/GAT.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Grid2D:
    n_pad: int  # padded node count (divisible by R*C)
    e_max: int  # max edges per device (padded)
    r_axes: Tuple[str, ...]  # destination-range axes (e.g. ("pod","data"))
    c_axis: str  # source-shard axis ("model")
    R: int
    C: int

    @property
    def sub(self) -> int:
        return self.n_pad // (self.R * self.C)

    @property
    def n_loc(self) -> int:  # state rows per device (model shard)
        return self.n_pad // self.C


def make_grid(mesh: Mesh, n: int, e_total: int) -> Grid2D:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    r_axes = tuple(a for a in ("pod", "data") if a in sizes)
    R = int(np.prod([sizes[a] for a in r_axes]))
    C = sizes["model"]
    n_pad = -(-n // (R * C)) * (R * C)
    # balanced-bucket assumption (paper §5.2 balls-into-bins): budget 1.5x
    e_max = -(-int(1.5 * e_total / (R * C)) // 128) * 128 + 128
    return Grid2D(n_pad, e_max, r_axes, "model", R, C)


# --- host-side bucketing ----------------------------------------------------


def _pos_in_layout(g: Grid2D, v: np.ndarray):
    """(shard c, local row) of vertex v in the interleaved Π-layout."""
    blk_r = g.n_pad // g.R
    c = (v % blk_r) // g.sub
    local = (v // blk_r) * g.sub + (v % g.sub)
    return c, local


def bucket_edges(g: Grid2D, src: np.ndarray, dst: np.ndarray,
                 coef: Optional[np.ndarray] = None):
    """Bucket edges onto the (R, C) grid.

    Returns (src_local, dst_local, coef, valid): each (R*C, e_max).
    Bucket of edge (u, v): c = source's model shard, r = v // (N/R).
    dst_local indexes a per-device (N/R,) partial buffer.
    """
    if coef is None:
        coef = np.ones(src.shape[0], np.float32)
    blk_r = g.n_pad // g.R
    c_src, src_loc = _pos_in_layout(g, src.astype(np.int64))
    r_dst = dst.astype(np.int64) // blk_r
    dst_loc = dst.astype(np.int64) % blk_r
    bucket = r_dst * g.C + c_src

    nb = g.R * g.C
    order = np.argsort(bucket, kind="stable")
    bucket_s = bucket[order]
    counts = np.bincount(bucket_s, minlength=nb)
    if counts.max() > g.e_max:
        raise ValueError(f"bucket overflow: {counts.max()} > {g.e_max}")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    out_src = np.zeros((nb, g.e_max), np.int32)
    out_dst = np.full((nb, g.e_max), blk_r, np.int32)  # pad -> dummy row
    out_coef = np.zeros((nb, g.e_max), np.float32)
    for b in range(nb):
        sl = order[starts[b]:starts[b] + counts[b]]
        out_src[b, :counts[b]] = src_loc[sl]
        out_dst[b, :counts[b]] = dst_loc[sl]
        out_coef[b, :counts[b]] = coef[sl]
    return out_src, out_dst, out_coef


def layout_features(g: Grid2D, x: np.ndarray) -> np.ndarray:
    """Permute (N, d) host features into the Π-layout (concat of S_c)."""
    n, d = x.shape
    xp = np.zeros((g.n_pad, d), x.dtype)
    xp[:n] = x
    blk_r = g.n_pad // g.R
    v = np.arange(g.n_pad)
    c, local = _pos_in_layout(g, v)
    out = np.zeros_like(xp)
    out_index = c * g.n_loc + local
    out[out_index] = xp[v]
    return out


# --- device-side 2D GCN -----------------------------------------------------


def _gcn2d_local(g: Grid2D, n_layers: int, params, x_loc, src, dst, coef,
                 labels_loc, mask_loc):
    """Per-device GCN forward + CE loss. x_loc: (n_loc, d)."""
    blk_r = g.n_pad // g.R

    def propagate(h):  # h: (n_loc, dh) -> aggregated (n_loc, dh)
        m = h[src] * coef[:, None]  # local gather (E, dh)
        part = jax.ops.segment_sum(m, dst, num_segments=blk_r + 1)[:blk_r]
        # reduce over model (partial over src shards), scatter rows
        part = jax.lax.psum_scatter(part, g.c_axis, scatter_dimension=0,
                                    tiled=True)  # (blk_r/C, dh)
        # re-gather rows over the dst-range axes -> (n_loc, dh), Π-layout
        for ax in reversed(g.r_axes):
            part = jax.lax.all_gather(part, ax, axis=0, tiled=True)
        return part

    h = x_loc
    for i, w in enumerate(params["w"]):
        h = propagate(h @ w)
        if i + 1 < n_layers:
            h = jax.nn.relu(h)
    # masked CE over local rows; every row appears once per (model) fiber
    logz = jax.nn.logsumexp(h.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(h.astype(jnp.float32),
                               labels_loc[:, None], axis=-1)[:, 0]
    loss = jnp.sum(jnp.where(mask_loc, logz - gold, 0.0))
    cnt = jnp.sum(mask_loc.astype(jnp.float32))
    loss = jax.lax.psum(loss, g.c_axis)
    cnt = jax.lax.psum(cnt, g.c_axis)
    return loss / jnp.maximum(cnt, 1.0)


def build_gcn2d_loss(mesh: Mesh, g: Grid2D, n_layers: int):
    """Returns loss(params, batch) distributed on the 2D grid.

    batch: x (n_pad, d) P(model on rows); src/dst/coef (R*C, e_max)
    P((r_axes, c_axis) on dim 0); labels/mask (n_pad,) P(model).
    """
    edge_spec = P(g.r_axes + (g.c_axis,), None)
    state_spec = P(g.c_axis, None)
    vec_spec = P(g.c_axis)

    def local(params, x, src, dst, coef, labels, mask):
        return _gcn2d_local(g, n_layers, params,
                            x, src[0], dst[0], coef[0], labels, mask)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(), state_spec, edge_spec, edge_spec, edge_spec,
                  vec_spec, vec_spec),
        out_specs=P(),
        check_vma=False,
    )
    return fn


def abstract_inputs(mesh: Mesh, g: Grid2D, d_in: int):
    sds = jax.ShapeDtypeStruct
    edge_spec = NamedSharding(mesh, P(g.r_axes + (g.c_axis,), None))
    state = NamedSharding(mesh, P(g.c_axis, None))
    vec = NamedSharding(mesh, P(g.c_axis))
    return {
        "x": sds((g.n_pad, d_in), jnp.float32, sharding=state),
        "src": sds((g.R * g.C, g.e_max), jnp.int32, sharding=edge_spec),
        "dst": sds((g.R * g.C, g.e_max), jnp.int32, sharding=edge_spec),
        "coef": sds((g.R * g.C, g.e_max), jnp.float32, sharding=edge_spec),
        "labels": sds((g.n_pad,), jnp.int32, sharding=vec),
        "mask": sds((g.n_pad,), jnp.bool_, sharding=vec),
    }
