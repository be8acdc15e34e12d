"""Distributed MFBC batch step — Theorem 5.1 on the production mesh.

Mesh mapping (paper grid (p₁, p₂, p₃) = (√(p/c), √(p/c), c)):

* ``model`` axis ↔ p₁ — shards the adjacency's *row* (u) dimension and the
  state's vertex (v) dimension.
* ``data`` axis ↔ p₂ — shards the adjacency's *column* dimension and the
  state's source (s) dimension.
* ``pod`` axis ↔ p₃ = c — the replication factor: the adjacency is
  replicated across pods (its broadcast amortizes over all products and
  batches, exactly as in the Theorem 5.1 proof) and each pod owns a
  disjoint slice of the source batch.

Per-iteration collectives (per device, F = frontier, C = product):

1. ``all_gather(F, data, dim=0)``          ≈ nnz(F)/p_model     bytes
2. local generalized matmul (Pallas/VPU)   — no communication
3. monoid reduce-scatter over ``model``    ≈ nnz(C)/p_data      bytes
4. ``all_gather(C, data, dim=1)`` + slice  ≈ nnz(C)/p_model     bytes

Total ≈ (nnz(F) + 2·nnz(C))/√(p/c) per iteration — the Theorem 5.1 bound.
The monoid reduction uses the pmin/pmax + tie-masked psum pair from
``repro.spgemm.semiring`` (DESIGN.md §3).

State layout: every (nb, n) matrix is P((pod, data), model) — sources over
pod×data, vertices over model. The adjacency (and its transpose, needed by
the backward MFBr sweep on directed graphs) is P(model, data), *no* pod
entry = replicated across pods.

Vertex id layout: the reduce-scatter(model) + all-gather(data) pipeline in
step 3–4 produces state columns in the *interleaved* order
``v(m; d', j) = d'·n/D + m·n/(D·M) + j`` (D, M = data/model axis sizes) for
the device's model index m. We adopt this as the canonical on-device vertex
order: the adjacency's **rows** are pre-permuted on the host with
``vertex_row_permutation`` so that contiguous P(model, ·) row blocks
enumerate exactly that order, local ids come from the closed form above,
and the host applies the inverse permutation to λ at the end. (CTF calls
this a cyclic-blocked layout; it is communication-free by construction.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core import monoids
from repro.core.monoids import Centpath, Multpath

INF = jnp.inf


@dataclasses.dataclass(frozen=True)
class BCMeshConfig:
    """Static configuration of the distributed BC step."""

    n: int  # padded vertex count (divisible by data*model and model*data)
    nb: int  # global batch size (divisible by pod*data)
    iters_bf: int  # static forward iteration bound (≥ weighted diameter)
    iters_br: int  # static backward bound
    data_axis: str = "data"
    model_axis: str = "model"
    pod_axis: Optional[str] = "pod"  # None on single-pod meshes
    block: int = 512  # local relax block size
    use_kernel: bool = False  # route local relax through Pallas kernels
    unroll: bool = False  # python-loop iterations (dry-run cost fidelity)

    @property
    def batch_axes(self):
        return ((self.pod_axis, self.data_axis) if self.pod_axis
                else (self.data_axis,))

    def specs(self):
        state = P(self.batch_axes, self.model_axis)
        adj = P(self.model_axis, self.data_axis)
        src = P(self.batch_axes)
        lam = P(self.model_axis)
        return state, adj, src, lam


def _local_relax_mp(cfg, F: Multpath, a_loc) -> Multpath:
    if cfg.use_kernel:
        from repro.kernels import ops as kops

        w, m = kops.multpath_matmul(F.w, F.m, a_loc)
        return Multpath(w, m)
    return monoids.multpath_relax_dense(F, a_loc, block=cfg.block,
                                        unroll=cfg.unroll)


def _local_relax_cp(cfg, F: Centpath, at_loc) -> Centpath:
    if cfg.use_kernel:
        from repro.kernels import ops as kops

        w, p, c = kops.centpath_matmul(F.w, F.p, at_loc)
        return Centpath(w, p, c)
    return monoids.centpath_relax_dense(F, at_loc, block=cfg.block,
                                         unroll=cfg.unroll)


def _reduce_scatter_gather(cfg, tree, reduce_fn):
    """Steps 3+4: ⊕-reduce over model (scatter v), re-gather v over data.

    Input leaves: (nb_pod, n/data) partial over model.
    Output leaves: (nb_pod, n/model) replicated over data.
    """
    red = reduce_fn(tree, cfg.model_axis)  # full reduce (pmin/pmax+psum)
    m_idx = jax.lax.axis_index(cfg.model_axis)
    m_sz = jax.lax.axis_size(cfg.model_axis)

    def scatter(v):
        blk = v.shape[1] // m_sz
        return jax.lax.dynamic_slice_in_dim(v, m_idx * blk, blk, axis=1)

    sc = jax.tree.map(scatter, red)  # (nb_pod, n/(data*model))
    return jax.tree.map(
        lambda v: jax.lax.all_gather(v, cfg.data_axis, axis=1, tiled=True),
        sc)  # (nb_pod, n/model)


def _slice_rows(cfg, tree):
    """Keep this device's source rows: (nb_pod, x) -> (nb_pod/data, x)."""
    d_idx = jax.lax.axis_index(cfg.data_axis)
    d_sz = jax.lax.axis_size(cfg.data_axis)

    def slc(v):
        blk = v.shape[0] // d_sz
        return jax.lax.dynamic_slice_in_dim(v, d_idx * blk, blk, axis=0)

    return jax.tree.map(slc, tree)


def _gather_rows(cfg, tree):
    """(nb_pod/data, x) -> (nb_pod, x): step 1 frontier broadcast."""
    return jax.tree.map(
        lambda v: jax.lax.all_gather(v, cfg.data_axis, axis=0, tiled=True),
        tree)


def _mp_axis_reduce(x: Multpath, axis: str) -> Multpath:
    wmin = jax.lax.pmin(x.w, axis)
    m = jax.lax.psum(jnp.where((x.w == wmin) & jnp.isfinite(wmin), x.m, 0.0),
                     axis)
    return Multpath(wmin, m)


def _cp_axis_reduce(x: Centpath, axis: str) -> Centpath:
    wmax = jax.lax.pmax(x.w, axis)
    tie = (x.w == wmax) & jnp.isfinite(wmax)
    return Centpath(wmax, jax.lax.psum(jnp.where(tie, x.p, 0.0), axis),
                    jax.lax.psum(jnp.where(tie, x.c, 0.0), axis))


def _dist_relax_mp(cfg, F_state: Multpath, a_loc) -> Multpath:
    """One distributed MFBF relaxation (steps 1–4)."""
    Fg = _gather_rows(cfg, F_state)  # (nb_pod, n/model)
    C_part = _local_relax_mp(cfg, Fg, a_loc)  # (nb_pod, n/data), partial
    C = _reduce_scatter_gather(cfg, C_part, _mp_axis_reduce)
    return _slice_rows(cfg, C)  # (nb_pod/data, n/model)


def _dist_relax_cp(cfg, F_state: Centpath, at_loc) -> Centpath:
    Fg = _gather_rows(cfg, F_state)
    C_part = _local_relax_cp(cfg, Fg, at_loc)
    C = _reduce_scatter_gather(cfg, C_part, _cp_axis_reduce)
    return _slice_rows(cfg, C)


def _count_children(cfg, Tw_state, at_loc):
    """Distributed SP-DAG child count.

    c0(s, v) = #{u : Tw(s,v) + A(v,u) == Tw(s,u)}. Reuses the centpath
    relax over A^T: contributions from u where Tw(s,u) - A(v,u) == Tw(s,v)
    land at v with count 1 each. Unreachable entries (+inf) are masked to
    the centpath identity (-inf) first — +inf would win the max-select.
    """
    w = jnp.where(jnp.isfinite(Tw_state), Tw_state, -INF)
    F = Centpath(w, jnp.zeros_like(Tw_state), jnp.zeros_like(Tw_state))
    Pc = _dist_relax_cp(cfg, F, at_loc)
    hit = (Pc.w == Tw_state) & jnp.isfinite(Tw_state) & (Pc.c > 0)
    return jnp.where(hit, Pc.c, 0.0).astype(jnp.int32)


def _local_ids(cfg, n):
    """Global vertex ids of this device's state columns (interleaved order).

    Column c of a state shard on model index m maps to
    v = d'·(n/D) + m·(n/(D·M)) + j with d' = c // (n/(D·M)), j = c % ….
    """
    m_idx = jax.lax.axis_index(cfg.model_axis)
    d_sz = jax.lax.axis_size(cfg.data_axis)
    m_sz = jax.lax.axis_size(cfg.model_axis)
    n_loc = n // m_sz
    sub = n // (d_sz * m_sz)
    c = jax.lax.iota(jnp.int32, n_loc)
    return (c // sub) * (n // d_sz) + m_idx * sub + (c % sub)


def _seed_multpath(cfg, sources_loc, n):
    """Local seed frontier: (s, u) = (0, 1) iff u == source_s."""
    u_ids = _local_ids(cfg, n)
    hit = sources_loc[:, None] == u_ids[None, :]
    return Multpath(jnp.where(hit, 0.0, INF).astype(jnp.float32),
                    jnp.where(hit, 1.0, 0.0).astype(jnp.float32))


def _batch_delta_local(cfg: BCMeshConfig, a_loc, at_loc, sources_loc,
                       valid_loc):
    """The full Algorithm 3 batch, local (per-device) view.

    Returns ``(contrib, mask)`` with ``contrib[s, v] = δ_s(v)`` for this
    device's source rows and vertex columns (zeroed on unreachable and
    padding entries) and ``mask[s, v] = [v reachable from s ∧ s valid]``.
    The Σδ-only (``_batch_step_local``) and moments
    (``_batch_step_moments_local``) entry points share this body; only
    their final reductions differ.
    """
    n = cfg.n
    # ---- MFBF ----
    seed = _seed_multpath(cfg, sources_loc, n)
    T = _dist_relax_mp(cfg, seed, a_loc)  # direct edges (paper line 1)
    F = T

    def bf_body(_, state):
        T, F = state
        C = _dist_relax_mp(cfg, F, a_loc)
        T_new = monoids.multpath_combine(T, C)
        keep = (C.w == T_new.w) & jnp.isfinite(C.w) & (C.m > 0)
        F_new = Multpath(jnp.where(keep, C.w, INF),
                         jnp.where(keep, C.m, 0.0))
        return T_new, F_new

    if cfg.unroll:
        st = (T, F)
        for _ in range(cfg.iters_bf):
            st = bf_body(0, st)
        T, _ = st
    else:
        T, _ = jax.lax.fori_loop(0, cfg.iters_bf, bf_body, (T, F))

    # ---- mask the t = s destination ----
    ids = _local_ids(cfg, n)
    self_col = sources_loc[:, None] == ids[None, :]
    Tw = jnp.where(self_col, INF, T.w)
    Tm_safe = jnp.where(self_col | (T.m <= 0), 1.0, T.m)
    finite = jnp.isfinite(Tw)

    # ---- MFBr ----
    c0 = _count_children(cfg, Tw, at_loc)
    Zp = jnp.zeros_like(Tw)
    seed_mask = finite & (c0 == 0)

    def mk_frontier(mask, Zp):
        return Centpath(jnp.where(mask, Tw, -INF),
                        jnp.where(mask, Zp + 1.0 / Tm_safe, 0.0),
                        jnp.where(mask, 1.0, 0.0))

    state0 = (Zp, c0, seed_mask, mk_frontier(seed_mask, Zp))

    def br_body(_, st):
        Zp, c, done, Fc = st
        Pc = _dist_relax_cp(cfg, Fc, at_loc)
        contrib = (Pc.w == Tw) & finite & (Pc.c > 0)
        Zp = Zp + jnp.where(contrib, Pc.p, 0.0)
        c = c - jnp.where(contrib, Pc.c.astype(c.dtype), 0)
        newly = finite & (c == 0) & (~done)
        return Zp, c, done | newly, mk_frontier(newly, Zp)

    if cfg.unroll:
        st = state0
        for _ in range(cfg.iters_br):
            st = br_body(0, st)
        Zp, _, _, _ = st
    else:
        Zp, _, _, _ = jax.lax.fori_loop(0, cfg.iters_br, br_body, state0)

    mask = finite & valid_loc[:, None]
    contrib = jnp.where(mask, Zp * T.m, 0.0)
    return contrib, mask


def _batch_step_local(cfg: BCMeshConfig, a_loc, at_loc, sources_loc,
                      valid_loc):
    """Σδ-only batch step (the exact all-sources sweep's reduction)."""
    contrib, _ = _batch_delta_local(cfg, a_loc, at_loc, sources_loc,
                                    valid_loc)
    # λ accumulation: sum over local sources, then over the batch axes.
    lam_part = jnp.sum(contrib, axis=0)  # (n/model,)
    return jax.lax.psum(lam_part, cfg.batch_axes)


def _batch_step_moments_local(cfg: BCMeshConfig, a_loc, at_loc, sources_loc,
                              valid_loc):
    """Moments batch step: per-vertex (Σδ, Σδ², n_reach) over the batch.

    The mesh analogue of ``core.mfbc.mfbc_batch_moments``: instead of
    folding sources into a pre-summed λ, the step keeps the per-source
    dependency rows long enough to also square them, then reduces all
    three statistics in a *single* stacked ``psum`` over the batch axes —
    one fused all-reduce of 3·n/model floats per batch, not a second
    collective per source. This is what lets the adaptive approximate-BC
    estimator run empirical-Bernstein/CLT stopping at pod scale (ROADMAP
    "Distributed sampling epochs with second moments").
    """
    contrib, mask = _batch_delta_local(cfg, a_loc, at_loc, sources_loc,
                                       valid_loc)
    stats = jnp.stack([
        jnp.sum(contrib, axis=0),                       # S1 = Σ_s δ_s(v)
        jnp.sum(contrib * contrib, axis=0),             # S2 = Σ_s δ_s(v)²
        jnp.sum(mask, axis=0).astype(jnp.float32),      # n_reach
    ])  # (3, n/model)
    return jax.lax.psum(stats, cfg.batch_axes)


def _batch_step_moments_segmented_local(cfg: BCMeshConfig, n_slots: int,
                                        a_loc, at_loc, sources_loc,
                                        valid_loc, slots_loc):
    """Segment-reduced moments step: per-slot (Σδ, Σδ², n_reach).

    The cross-request fusion primitive on the mesh (the distributed
    counterpart of ``core.mfbc.mfbc_batch_moments_segmented``): each
    device segment-sums its local source rows into ``(n_slots, n/model)``
    per-slot statistics (rows tagged ``slots_loc == n_slots`` are padding
    and land in a dump segment that is dropped), then all three
    statistics for *all* slots ride one stacked ``psum`` over the batch
    axes — a fused batch packing many queries still costs exactly one
    collective of ``3·n_slots·n/p_model`` floats, which is the whole
    point of fusing under-filled per-request batches.
    """
    contrib, mask = _batch_delta_local(cfg, a_loc, at_loc, sources_loc,
                                       valid_loc)
    seg = functools.partial(jax.ops.segment_sum, segment_ids=slots_loc,
                            num_segments=n_slots + 1)
    stats = jnp.stack([
        seg(contrib)[:n_slots],                         # S1 per slot
        seg(contrib * contrib)[:n_slots],               # S2 per slot
        seg(mask.astype(jnp.float32))[:n_slots],        # n_reach per slot
    ])  # (3, n_slots, n/model)
    return jax.lax.psum(stats, cfg.batch_axes)


def build_mfbc_step(mesh: Mesh, cfg: BCMeshConfig, *, moments: bool = False,
                    segments: Optional[int] = None):
    """Returns a jit'd distributed batch step on ``mesh``.

    a / a_t: (n, n) dense adjacency and its transpose, laid out
    P(model, data) (replicated over pod). sources/valid: (nb,) laid out
    P((pod, data)).

    With ``moments=False`` the step returns λ: (n,) sharded over model
    (the exact sweep's Σδ). With ``moments=True`` it returns a (3, n)
    stack of (Σδ, Σδ², n_reach) sharded over model in the vertex
    dimension — the distributed counterpart of
    ``core.mfbc.mfbc_batch_moments``. With ``segments=n_slots`` the step
    additionally takes per-row slot ids (same P((pod, data)) layout as
    the sources) and returns a (3, n_slots, n) stack segment-reduced per
    slot — the fused cross-request batch step.
    """
    state_spec, adj_spec, src_spec, lam_spec = cfg.specs()
    if segments is not None:
        fn = shard_map(
            functools.partial(_batch_step_moments_segmented_local, cfg,
                              segments),
            mesh=mesh,
            in_specs=(adj_spec, adj_spec, src_spec, src_spec, src_spec),
            out_specs=P(None, None, cfg.model_axis),
            check_vma=False,
        )
        return jax.jit(fn)
    body = _batch_step_moments_local if moments else _batch_step_local
    out_spec = P(None, cfg.model_axis) if moments else lam_spec
    fn = shard_map(
        functools.partial(body, cfg),
        mesh=mesh,
        in_specs=(adj_spec, adj_spec, src_spec, src_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return jax.jit(fn)


def input_shardings(mesh: Mesh, cfg: BCMeshConfig):
    _, adj_spec, src_spec, _ = cfg.specs()
    return (NamedSharding(mesh, adj_spec), NamedSharding(mesh, adj_spec),
            NamedSharding(mesh, src_spec), NamedSharding(mesh, src_spec))


# --------------------------------------------------------------------------
# Host-side helpers: padding, row permutation, full-graph driver.
# --------------------------------------------------------------------------


def vertex_row_permutation(n: int, d_sz: int, m_sz: int):
    """Π such that A[Π, :] sharded P(model, ·) has row blocks matching the
    interleaved on-device vertex order (see module docstring)."""
    import numpy as np

    sub = n // (d_sz * m_sz)
    perm = np.empty(n, dtype=np.int64)
    i = 0
    for m in range(m_sz):
        for d in range(d_sz):
            base = d * (n // d_sz) + m * sub
            perm[i:i + sub] = np.arange(base, base + sub)
            i += sub
    return perm


class MeshBCContext:
    """Device-resident mesh state shared across batch-size buckets.

    Pads and permutes the adjacency once, uploads A and Aᵀ once, and
    hands out jitted batch steps per ``(nb, variant)`` from a cache — so
    one executor can serve several padded batch sizes (the power-of-two
    bucket set of ``repro.bc``) and the segmented fusion variant without
    re-uploading the adjacency or retracing already-compiled shapes.
    ``prepare_mesh_batch_step`` remains as the single-``nb`` convenience
    wrapper over this class.

    ``g`` is a ``Graph`` (adjacency uploaded eagerly) or anything
    stats-like with an ``n`` attribute but no edge arrays (e.g.
    ``repro.graphs.formats.GraphStats``): the context then comes up with
    *no* adjacency resident, and the caller streams it in through
    ``upload_coo_chunks`` / ``graphs.formats.build_sharded_adjacency``.
    That path densifies the adjacency one device shard at a time — the
    host never holds the full (n_pad, n_pad) matrix, which is what makes
    scale-18+ graphs loadable at all.
    """

    def __init__(self, g, mesh: Mesh, *, iters: int = 0,
                 use_kernel: bool = False, block: int = 512,
                 execution=None):
        # Duck-typed backend-dispatch config (repro.bc.ExecutionConfig):
        # the core layer never imports the solver facade, it just reads
        # the three relax-step fields. The mesh step is dense-only.
        if execution is not None:
            backend = getattr(execution, "backend", None)
            if backend is not None and str(getattr(backend, "value",
                                                   backend)) != "dense":
                raise ValueError("MeshBCContext supports only the dense "
                                 "backend")
            if execution.use_kernel is not None:
                use_kernel = bool(execution.use_kernel)
            block = int(execution.block)

        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.mesh = mesh
        self.n = g.n
        self._d_sz = axis_sizes["data"]
        self._m_sz = axis_sizes["model"]
        self._pod = "pod" if "pod" in axis_sizes else None
        self._p_sz = axis_sizes.get("pod", 1)
        self.chunk = self._p_sz * self._d_sz  # source-batch divisibility
        self.iters = iters if iters > 0 else g.n
        self._use_kernel = use_kernel
        self._block = block

        lcm = self._d_sz * self._m_sz
        self.n_pad = -(-g.n // lcm) * lcm
        self.perm = vertex_row_permutation(self.n_pad, self._d_sz, self._m_sz)
        # Shardings depend only on axis names, not on nb: one probe cfg.
        self._sh_a, self._sh_at, self._sh_src, self._sh_val = \
            input_shardings(mesh, self._cfg(self.chunk))
        self._a_dev = None
        self._at_dev = None
        self._steps = {}  # (nb_pad, variant, n_slots) -> jitted step
        if hasattr(g, "src"):
            self.upload_graph(g)

    # -- adjacency upload ----------------------------------------------------
    def upload_graph(self, g) -> "MeshBCContext":
        """Upload a host-resident ``Graph``'s adjacency (one chunk)."""
        return self.upload_coo_chunks([(g.src, g.dst, g.w)])

    def upload_coo_chunks(self, chunks) -> "MeshBCContext":
        """Build the device-sharded A / Aᵀ from streamed COO chunks.

        Each ``(src, dst, w)`` chunk is routed to the per-device shard
        blocks it intersects; blocks densify lazily inside
        ``jax.make_array_from_callback``, so peak host memory is
        O(nnz + one shard block), never O(n²). Duplicate arcs fold by
        ``min`` and self loops are dropped — bitwise the semantics of
        ``coo_to_dense`` (+ inf diagonal) on the concatenated stream,
        for any chunking.
        """
        import numpy as np

        rb = self.n_pad // self._m_sz  # shard rows  (model axis)
        cb = self.n_pad // self._d_sz  # shard cols  (data axis)
        inv_perm = np.empty(self.n_pad, dtype=np.int64)
        inv_perm[self.perm] = np.arange(self.n_pad)
        buckets_a: dict = {}
        buckets_at: dict = {}
        for src, dst, w in chunks:
            src = np.asarray(src, dtype=np.int64)
            dst = np.asarray(dst, dtype=np.int64)
            w = np.asarray(w, dtype=np.float32)
            keep = src != dst  # A(i, i) = inf structurally
            src, dst, w = src[keep], dst[keep], w[keep]
            if src.shape[0] and int(max(src.max(), dst.max())) >= self.n:
                raise ValueError("vertex id out of range for this context")
            # A[perm, :]: arc (s, d) lands at row inv_perm[s], col d.
            self._bucket(buckets_a, inv_perm[src], dst, w, rb, cb)
            # Aᵀ[perm, :]: arc (s, d) lands at row inv_perm[d], col s.
            self._bucket(buckets_at, inv_perm[dst], src, w, rb, cb)
        self._a_dev = self._densify(buckets_a, rb, cb, self._sh_a)
        self._at_dev = self._densify(buckets_at, rb, cb, self._sh_at)
        return self

    @staticmethod
    def _bucket(buckets, rows, cols, w, rb, cb) -> None:
        """Split one chunk's entries by the (row, col) shard block."""
        import numpy as np

        if rows.shape[0] == 0:
            return
        bid = (rows // rb) * (1 << 20) + cols // cb
        order = np.argsort(bid, kind="stable")
        bid, rows, cols, w = bid[order], rows[order], cols[order], w[order]
        cuts = np.nonzero(bid[1:] != bid[:-1])[0] + 1
        for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, bid.shape[0]]):
            key = (int(rows[lo]) // rb, int(cols[lo]) // cb)
            buckets.setdefault(key, []).append(
                (rows[lo:hi] % rb, cols[lo:hi] % cb, w[lo:hi]))

    def _densify(self, buckets, rb, cb, sharding):
        import numpy as np

        def shard(index):
            r0 = index[0].start or 0
            c0 = index[1].start or 0
            blk = np.full((rb, cb), np.inf, dtype=np.float32)
            for rows, cols, w in buckets.get((r0 // rb, c0 // cb), ()):
                np.minimum.at(blk, (rows, cols), w)
            return blk

        return jax.make_array_from_callback(
            (self.n_pad, self.n_pad), sharding, shard)

    def _adjacency(self):
        if self._a_dev is None:
            raise RuntimeError(
                "MeshBCContext has no adjacency resident: built from stats "
                "only — stream the graph in with upload_coo_chunks() / "
                "graphs.formats.build_sharded_adjacency() first")
        return self._a_dev, self._at_dev

    def round_nb(self, nb: int) -> int:
        """Smallest pod·data multiple ≥ nb (the mesh batch divisibility)."""
        return -(-nb // self.chunk) * self.chunk

    def _cfg(self, nb_pad: int) -> BCMeshConfig:
        return BCMeshConfig(n=self.n_pad, nb=nb_pad, iters_bf=self.iters,
                            iters_br=self.iters, pod_axis=self._pod,
                            use_kernel=self._use_kernel, block=self._block)

    def _step(self, nb_pad: int, variant: str, n_slots: Optional[int] = None):
        key = (nb_pad, variant, n_slots)
        if key not in self._steps:
            cfg = self._cfg(nb_pad)
            if variant == "segmented":
                self._steps[key] = build_mfbc_step(self.mesh, cfg,
                                                   segments=n_slots)
            else:
                self._steps[key] = build_mfbc_step(
                    self.mesh, cfg, moments=(variant == "moments"))
        return self._steps[key]

    def _pad_inputs(self, nb_pad: int, sources, valid,
                    slot_ids=None, n_slots: int = 0):
        import numpy as np

        src = np.zeros(nb_pad, np.int32)
        val = np.zeros(nb_pad, bool)
        k = min(sources.shape[0], nb_pad)
        src[:k], val[:k] = sources[:k], valid[:k]
        out = [jax.device_put(jnp.asarray(src), self._sh_src),
               jax.device_put(jnp.asarray(val), self._sh_val)]
        if slot_ids is not None:
            # Padding rows land in the dump segment n_slots (dropped).
            sid = np.full(nb_pad, n_slots, np.int32)
            sid[:k] = slot_ids[:k]
            out.append(jax.device_put(jnp.asarray(sid), self._sh_src))
        return out

    def run_sum(self, sources, valid, *, nb: int):
        """Σδ-only batch contribution, original vertex order, length n."""
        import numpy as np

        nb_pad = self.round_nb(nb)
        a_dev, at_dev = self._adjacency()
        src, val = self._pad_inputs(nb_pad, sources, valid)
        lam_b = self._step(nb_pad, "sum")(a_dev, at_dev, src, val)
        lam = np.zeros(self.n_pad, dtype=np.float64)
        lam[self.perm] = np.asarray(lam_b, np.float64)  # undo permutation
        return lam[:self.n]

    def run_moments(self, sources, valid, *, nb: int):
        """(S1, S2, n_reach) per vertex — the sampling-epoch reduction."""
        import numpy as np

        nb_pad = self.round_nb(nb)
        a_dev, at_dev = self._adjacency()
        src, val = self._pad_inputs(nb_pad, sources, valid)
        stats_b = self._step(nb_pad, "moments")(a_dev, at_dev, src, val)
        stats = np.zeros((3, self.n_pad), dtype=np.float64)
        stats[:, self.perm] = np.asarray(stats_b, np.float64)
        return (stats[0, :self.n], stats[1, :self.n],
                stats[2, :self.n].astype(np.int64))

    def run_segmented(self, sources, valid, slot_ids, n_slots: int, *,
                      nb: int):
        """Per-slot (S1, S2, n_reach), each (n_slots, n) — fused batches."""
        import numpy as np

        nb_pad = self.round_nb(nb)
        a_dev, at_dev = self._adjacency()
        src, val, sid = self._pad_inputs(nb_pad, sources, valid,
                                         slot_ids, n_slots)
        stats_b = self._step(nb_pad, "segmented", n_slots)(
            a_dev, at_dev, src, val, sid)
        stats = np.zeros((3, n_slots, self.n_pad), dtype=np.float64)
        stats[:, :, self.perm] = np.asarray(stats_b, np.float64)
        return (stats[0, :, :self.n], stats[1, :, :self.n],
                stats[2, :, :self.n].astype(np.int64))


def prepare_mesh_batch_step(g, mesh: Mesh, *, nb: int, iters: int = 0,
                            use_kernel: bool = False, block: int = 512,
                            moments: bool = False):
    """Single-``nb`` convenience wrapper over ``MeshBCContext``.

    Returns ``(run, nb_pad)`` where ``run`` takes host arrays of up to
    ``nb_pad`` sources (shorter inputs are zero-padded with
    ``valid=False``) and returns results in *original* vertex order,
    length ``g.n``:

    * ``moments=False`` (the Σδ-only reduction):
      ``run(sources, valid) -> λ_partial`` — the batch's Σδ contribution,
      float64 (n,). This is what the unified ``repro.bc`` exact sweep
      runs (``MeshExecutor.step_sum``): one n/p_model all-reduce per
      batch instead of the moments step's 3× stacked one.
    * ``moments=True`` (the adaptive approximate-BC driver): ``run(sources,
      valid) -> (S1, S2, n_reach)`` with ``S1(v) = Σ_s δ_s(v)`` and
      ``S2(v) = Σ_s δ_s(v)²`` over the batch's valid sources and
      ``n_reach(v)`` the count of sources that reach v — the same
      (Σδ, Σδ²) contract as ``core.mfbc.mfbc_batch_moments``, so
      ``approx.driver.LambdaEstimator`` can run Bernstein/CLT stopping
      on the mesh path. The Σδ² reduction rides the same fused all-reduce
      as Σδ (see ``_batch_step_moments_local``), so the extra
      communication is one stacked psum per batch.

    Callers that serve several batch sizes (or the segmented fused step)
    should hold a ``MeshBCContext`` directly — this wrapper builds a
    fresh context, so the adjacency upload is not shared across calls.
    """
    ctx = MeshBCContext(g, mesh, iters=iters, use_kernel=use_kernel,
                        block=block)
    nb_pad = ctx.round_nb(nb)
    if moments:
        return (lambda s, v: ctx.run_moments(s, v, nb=nb_pad)), nb_pad
    return (lambda s, v: ctx.run_sum(s, v, nb=nb_pad)), nb_pad


def dist_mfbc(g, mesh: Mesh, *, nb: int, iters: int = 0,
              use_kernel: bool = False, block: int = 512):
    """Deprecated: use ``repro.bc.solve(g, BCQuery(mode="exact"), mesh=...)``.

    Thin shim kept for one release: the exact all-sources mesh sweep is
    now one of the two ``repro.bc`` drivers (a ``MeshExecutor`` under the
    exact sweep — same batches, same Theorem 5.1 step, λ = Σ S1).
    """
    import warnings

    warnings.warn(
        "core.dist_bc.dist_mfbc is deprecated; use repro.bc.solve with "
        "BCQuery(mode='exact', ...) and a mesh", DeprecationWarning,
        stacklevel=2)
    from repro.bc import BCQuery, ExecutionConfig, solve

    query = BCQuery(mode="exact", n_b=nb, iters=iters,
                    execution=ExecutionConfig(use_kernel=use_kernel,
                                              block=block))
    return solve(g, query, mesh=mesh).lam
