"""Executors — one batch-step protocol over every BC backend.

A ``BatchExecutor`` turns a padded source batch into per-vertex
dependency statistics through three methods: ``step(sources, valid) ->
(S1, S2, n_reach)`` with ``S1(v) = Σ_s δ_s(v)`` and
``S2(v) = Σ_s δ_s(v)²`` over the batch's valid sources (the (Σδ, Σδ²)
contract of ``approx.driver.LambdaEstimator``, what the sampling epochs
call), ``step_sum(sources, valid) -> S1`` (the exact sweep's Σδ-only
reduction, skipping the moments overhead), and ``step_segmented(sources,
valid, slot_ids, n_slots) -> (S1, S2, n_reach)`` shaped ``(n_slots, n)``
— the cross-request fusion primitive: one device call (one fused
all-reduce on the mesh) serving a batch packed from several concurrent
queries, segment-reduced per slot. Both drivers in ``repro.bc.solve``
run over this one protocol, so "exact vs approx" and "single host vs
mesh" are orthogonal choices, and ``serve.bc_service`` fuses requests
over it without branching on placement.

Shape bucketing: ``step`` / ``step_sum`` pad to the plan's ``n_b``
exactly (so single-query results are bit-stable across releases), while
``step_segmented`` pads to the smallest power-of-two bucket ≥ the batch
length (``plan.buckets``, see ``planner.bucket_sizes``) — one executor
serves many ragged fused batch sizes with a bounded set of compiled
shapes instead of a retrace per length or an always-pad-to-``n_b``.

``SingleHostExecutor`` is the former ``approx.driver._single_host_step``
made public: dense or COO adjacency on one device, jitted
``core.mfbc.mfbc_batch_moments``. ``MeshExecutor`` holds one
``core.dist_bc.MeshBCContext`` (device-resident A/Aᵀ shared by every
bucket and variant; Theorem 5.1 collectives, fused (Σδ, Σδ², n_reach)
all-reduce); its ``n_b`` is the mesh-divisible rounded-up batch size,
which callers must use when sizing sample batches.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Protocol, Tuple, Union, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.bc.config import Backend, as_backend
from repro.bc.planner import BCPlan, bucket_sizes
from repro.core.adjacency import (CsrAdj, coo_adj_from_graph,
                                  csr_adj_from_graph, dense_adj_from_graph)
from repro.core.metrics import components_graph, components_labels
from repro.core.mfbf import TRACE_CAP
from repro.core.mfbc import (metric_batch_moments,
                             metric_batch_moments_segmented, mfbc_batch,
                             mfbc_batch_moments,
                             mfbc_batch_moments_segmented,
                             mfbc_batch_moments_traced)
from repro.graphs.formats import Graph

Moments = Tuple[np.ndarray, np.ndarray, np.ndarray]  # (S1, S2, n_reach)


# --- backend registry ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """How one ``Backend`` plugs into the executor layer.

    ``make_adjacency(g, plan)`` builds the device-resident adjacency the
    single-host relax steps dispatch on (``core.adjacency.DenseAdj`` /
    ``CooAdj`` — the jitted ``core.mfbc`` batch functions branch on its
    type, so one factory is the whole backend-specific surface here);
    ``placements`` lists where the backend can run (only DENSE has a
    distributed Theorem 5.1 step); ``supports_kernel`` gates the Pallas
    kernel route (COO's segment ops have no kernel variant).
    """

    backend: Backend
    make_adjacency: Callable[[Graph, BCPlan], Any]
    placements: Tuple[str, ...] = ("single_host",)
    supports_kernel: bool = False


_BACKEND_REGISTRY: Dict[Backend, BackendSpec] = {}


def register_backend(spec: BackendSpec) -> BackendSpec:
    """Register (or replace) the executor-layer spec for a backend."""
    _BACKEND_REGISTRY[spec.backend] = spec
    return spec


def backend_spec(backend: Union[Backend, str]) -> BackendSpec:
    """Resolve a backend (enum or legacy string) to its registered spec."""
    be = as_backend(backend)
    try:
        return _BACKEND_REGISTRY[be]
    except KeyError:
        raise ValueError(f"no executor registered for backend "
                         f"{be.value!r}") from None


def registered_backends() -> Tuple[Backend, ...]:
    return tuple(_BACKEND_REGISTRY)


register_backend(BackendSpec(
    backend=Backend.DENSE,
    make_adjacency=lambda g, plan: dense_adj_from_graph(
        g, block=plan.block, use_kernel=plan.use_kernel),
    placements=("single_host", "mesh"),
    supports_kernel=True))

register_backend(BackendSpec(
    backend=Backend.COO,
    make_adjacency=lambda g, plan: coo_adj_from_graph(g),
    placements=("single_host",)))

register_backend(BackendSpec(
    backend=Backend.CSR,
    # The plan's n_b sizes the compaction capacity ladder: the frontier
    # buckets bound (batch row, vertex) slots, so the batch axis is part
    # of the capacity math (see core.adjacency.frontier_caps).
    make_adjacency=lambda g, plan: csr_adj_from_graph(g, n_b=plan.n_b),
    placements=("single_host",)))


@runtime_checkable
class BatchExecutor(Protocol):
    """The one surface both solve drivers (exact sweep, epochs) run over."""

    n_b: int  # effective batch size (mesh executors round the plan's up)
    buckets: Tuple[int, ...]  # padded shapes served (ascending, max = n_b)
    plan: BCPlan

    def step(self, sources: np.ndarray, valid: np.ndarray, *,
             metric: str = "betweenness", hops: int = 0) -> Moments:
        """Per-vertex (Σδ, Σδ², n_reach) over the batch's valid sources.
        ``metric`` selects the per-source contribution formula
        (``core.metrics`` registry); the default is the original
        betweenness path, byte-for-byte."""
        ...

    def step_sum(self, sources: np.ndarray, valid: np.ndarray, *,
                 metric: str = "betweenness", hops: int = 0) -> np.ndarray:
        """Σδ only — the exact sweep's reduction, skipping the moments
        overhead (on the mesh: one n/p_model all-reduce instead of the
        3× stacked one). Built lazily, so approx-only callers never
        compile it."""
        ...

    def step_segmented(self, sources: np.ndarray, valid: np.ndarray,
                       slot_ids: np.ndarray, n_slots: int, *,
                       metrics=None, hops: int = 0) -> Moments:
        """Per-slot (Σδ, Σδ², n_reach), each ``(n_slots, n)`` — the fused
        cross-request batch: row tags ``slot_ids ∈ [0, n_slots)`` say
        which query each source belongs to. Slot j's statistics are
        bitwise what an unfused run of its rows (in the same order)
        would produce on the same executor. Batches are padded to the
        smallest serving bucket, not ``n_b``. ``metrics`` optionally
        names each slot's metric (length ``n_slots``; ``None`` means all
        betweenness) — the cross-metric fusion surface, restricted to
        slots whose sweep structures match (``core.metrics.fuse_group``).
        """
        ...

    def bucket_for(self, k: int) -> int:
        """The padded shape a k-source fused batch runs at."""
        ...

    def labels(self) -> np.ndarray:
        """Fixed-point metric entry (components): (n,) float64 min-label
        array over the zero-weight symmetrized structure, computed in
        one call. Single-host only."""
        ...


def _pad_batch(sources: np.ndarray, valid: np.ndarray, n_b: int):
    sources = np.asarray(sources, np.int32)
    valid = np.asarray(valid, bool)
    if sources.shape[0] > n_b:
        # Never truncate silently: dropped sources would bias any
        # estimator fed the full batch's n_valid.
        raise ValueError(f"batch of {sources.shape[0]} sources exceeds "
                         f"the executor's n_b={n_b}; split it or build "
                         f"an executor from a plan with a larger n_b")
    if sources.shape[0] == n_b:
        return sources, valid
    src = np.zeros(n_b, np.int32)
    val = np.zeros(n_b, bool)
    k = sources.shape[0]
    src[:k], val[:k] = sources[:k], valid[:k]
    return src, val


def _pad_segmented(sources, valid, slot_ids, bucket: int, pad_slot: int):
    """Pad a fused batch to its bucket; padding rows carry ``valid=False``
    and slot id ``pad_slot`` (the segment count the kernel runs with —
    its dump segment, dropped from the result)."""
    sources = np.asarray(sources, np.int32)
    valid = np.asarray(valid, bool)
    slot_ids = np.asarray(slot_ids, np.int32)
    if not (sources.shape == valid.shape == slot_ids.shape):
        raise ValueError("sources, valid and slot_ids must share one shape")
    k = sources.shape[0]
    if k == bucket:
        return sources, valid, slot_ids
    src = np.zeros(bucket, np.int32)
    val = np.zeros(bucket, bool)
    sid = np.full(bucket, pad_slot, np.int32)
    src[:k], val[:k], sid[:k] = sources, valid, slot_ids
    return src, val, sid


def _bucket_for(k: int, buckets: Tuple[int, ...], n_b: int) -> int:
    for b in buckets:
        if k <= b:
            return b
    raise ValueError(f"batch of {k} sources exceeds the executor's "
                     f"n_b={n_b}; split it (the BatchAssembler caps "
                     f"fused batches at executor capacity)")


def _slot_bucket(n_slots: int) -> int:
    """Segment-count bucket: next power of two ≥ n_slots.

    ``n_slots`` is a static jit argument, so compiling per exact slot
    count would retrace as requests retire (16, 15, 14, … live slots).
    Bucketing the slot dimension the same way as the batch dimension
    keeps the compiled-shape set at O(log buckets · log slots); the
    extra segments are empty and sliced off."""
    b = 1
    while b < n_slots:
        b <<= 1
    return b


_step_span = functools.partial(annotate_function, name="repro.executor.step")


class _ExecutorBase:
    """Shared padding/bucketing half of every ``BatchExecutor``.

    Subclasses set ``plan`` / ``n_b`` / ``buckets`` in ``__init__`` and
    implement the three raw compute hooks; the base owns the shape
    contract (exact-``n_b`` padding for ``step``/``step_sum``, bucket +
    slot-dim padding for ``step_segmented``) so both placements — and
    any future backend — pad identically and the fused-vs-unfused
    bitwise-parity property cannot drift between implementations.
    """

    plan: BCPlan
    n_b: int
    buckets: Tuple[int, ...]

    def bucket_for(self, k: int) -> int:
        return _bucket_for(k, self.buckets, self.n_b)

    # A batch runs under the host span ``repro.executor.step`` (pad,
    # dispatch, wait, pull), which a profiler trace puts on the device's
    # clock; with no profiler session it is a no-op.
    @_step_span
    def step(self, sources: np.ndarray, valid: np.ndarray, *,
             metric: str = "betweenness", hops: int = 0) -> Moments:
        src, val = _pad_batch(sources, valid, self.n_b)
        if metric == "betweenness":
            # the original path, byte-for-byte (including the CSR trace)
            return self._moments(src, val)
        return self._metric_moments(src, val, metric, hops)

    @_step_span
    def step_sum(self, sources: np.ndarray, valid: np.ndarray, *,
                 metric: str = "betweenness", hops: int = 0) -> np.ndarray:
        src, val = _pad_batch(sources, valid, self.n_b)
        if metric == "betweenness":
            return self._sum(src, val)
        return self._metric_moments(src, val, metric, hops)[0]

    def step_segmented(self, sources: np.ndarray, valid: np.ndarray,
                       slot_ids: np.ndarray, n_slots: int, *,
                       metrics=None, hops: int = 0) -> Moments:
        bucket = self.bucket_for(np.asarray(sources).shape[0])
        n_seg = _slot_bucket(n_slots)  # pad the slot dim too (jit-static)
        src, val, sid = _pad_segmented(sources, valid, slot_ids, bucket,
                                       n_seg)
        if metrics is None or all(m == "betweenness" for m in metrics):
            s1, s2, nr = self._segmented(src, val, sid, n_seg, bucket)
            return s1[:n_slots], s2[:n_slots], nr[:n_slots]
        if len(metrics) != n_slots:
            raise ValueError(f"metrics names {len(metrics)} slots, "
                             f"batch has {n_slots}")
        # static kinds tuple (first-appearance order) + per-row tags;
        # padding rows tag kind 0 — they are valid=False and land in the
        # dump segment regardless.
        kinds = tuple(dict.fromkeys(metrics))
        slot_kind = np.array([kinds.index(m) for m in metrics]
                             + [0], np.int32)  # [-1] = the dump segment
        mids = slot_kind[np.minimum(sid, len(metrics))]
        s1, s2, nr = self._metric_segmented(src, val, sid, mids, kinds,
                                            n_seg, bucket, hops)
        return s1[:n_slots], s2[:n_slots], nr[:n_slots]

    # -- compute hooks (padded inputs, full padded outputs) -------------
    def _moments(self, src, val) -> Moments:
        raise NotImplementedError

    def _sum(self, src, val) -> np.ndarray:
        raise NotImplementedError

    def _segmented(self, src, val, sid, n_seg: int, bucket: int) -> Moments:
        raise NotImplementedError

    # -- metric-generic hooks (betweenness never routes through these) --
    def _metric_moments(self, src, val, metric: str, hops: int) -> Moments:
        raise NotImplementedError(
            f"{type(self).__name__} runs betweenness only; metric "
            f"{metric!r} sweeps are single-host")

    def _metric_segmented(self, src, val, sid, mids, kinds, n_seg: int,
                          bucket: int, hops: int) -> Moments:
        raise NotImplementedError(
            f"{type(self).__name__} runs betweenness only; metrics "
            f"{kinds!r} fuse single-host")

    def labels(self) -> np.ndarray:
        raise NotImplementedError(
            f"{type(self).__name__} has no fixed-point metric entry "
            f"(components runs single-host)")


def _pull(*arrays):
    """Device-to-host copy of a batch's outputs in one transfer, under the
    host span ``repro.executor.pull``."""
    with TraceAnnotation("repro.executor.pull"):
        return jax.device_get(arrays)


class SingleHostExecutor(_ExecutorBase):
    """One-device moments step (dense blocked, COO, or frontier-compacted
    CSR segment-op relax).

    The adjacency comes from the plan's backend via the registry
    (``backend_spec``); the jitted ``core.mfbc`` batch functions
    dispatch on its type, so every backend shares each line above the
    relax. A ``CsrAdj`` adjacency additionally routes ``step`` and
    ``step_sum`` through the traced moments entry point and accumulates
    the frontier occupancy side channel (``occupancy_summary``).
    """

    def __init__(self, g: Graph, plan: BCPlan):
        self.plan = plan
        self.n_b = plan.n_b
        self.buckets = plan.buckets or bucket_sizes(plan.n_b)
        self._g = g
        self._adj = backend_spec(plan.backend).make_adjacency(g, plan)
        # Frontier-occupancy trace: collected only for the compacting
        # adjacency (the frontier-sparse engine's side channel); dense and
        # COO moments run the untraced jit path, byte-for-byte as before.
        self._trace = isinstance(self._adj, CsrAdj)
        self._occ: Dict[str, Any] = {}
        # Lazy second adjacency for the components fixed point (the
        # zero-weight symmetrized structure) — non-components callers
        # never build it.
        self._cc_adj = None

    def _record_occupancy(self, tr_bf, tr_br) -> None:
        """Fold one batch's host copies of both ``SweepTrace``s into the
        running summary, in Python ints."""
        def rows(tr):
            k = min(int(tr.iters), TRACE_CAP)
            return [[int(f), int(b), int(a), int(e)] for f, b, a, e in
                    zip(tr.fnnz[:k], tr.bucket[:k], tr.arcs[:k],
                        tr.entry_arcs[:k])]
        rows_bf, rows_br = rows(tr_bf), rows(tr_br)
        o = self._occ
        o["batches"] = o.get("batches", 0) + 1
        o["iters_bf"], o["iters_br"] = int(tr_bf.iters), int(tr_br.iters)
        o["per_iter_bf"] = [r[0] for r in rows_bf]
        o["per_iter_br"] = [r[0] for r in rows_br]
        o["rows_bf"], o["rows_br"] = rows_bf, rows_br
        o["fnnz_first"] = rows_bf[0][0] if rows_bf else 0
        o["fnnz_last"] = rows_bf[-1][0] if rows_bf else 0
        for key in ("overflows", "compact_hits", "frontier_arcs",
                    "arc_slots"):
            o[key] = (o.get(key, 0) + int(getattr(tr_bf, key))
                      + int(getattr(tr_br, key)))
        o["entry_arcs"] = (o.get("entry_arcs", 0)
                           + sum(r[3] for r in rows_bf + rows_br))
        for sweep, r, tr in (("bf", rows_bf, tr_bf), ("br", rows_br, tr_br)):
            o[f"entries_{sweep}"] = (o.get(f"entries_{sweep}", 0)
                                     + sum(x[0] for x in r))
            o[f"reached_{sweep}"] = (o.get(f"reached_{sweep}", 0)
                                     + int(tr.reached))
        o["relax_calls"] = (o.get("relax_calls", 0) + int(tr_bf.iters)
                            + int(tr_br.iters))
        calls = max(o["relax_calls"], 1)
        o["hit_rate"] = o["compact_hits"] / calls

    def occupancy_summary(self):
        """Accumulated frontier-occupancy trace, or None when not traced.

        From the most recent batch: the per-iteration profiles of the
        forward (``_bf``) and backward (``_br``) sweeps —
        ``per_iter_*`` the frontier nnz, ``rows_*`` the ``[fnnz, rung,
        arcs, entry_arcs]`` of each relax (rung ``len(caps)`` is the
        full-edge-list fallback). Accumulated over every traced batch
        this executor ran: ``batches``, ``relax_calls``, ``overflows``,
        ``compact_hits``, ``hit_rate``, ``frontier_arcs`` (arcs leaving
        the union frontier), ``arc_slots`` (arc slots the chosen
        branches processed; ``frontier_arcs / arc_slots`` is the relax's
        useful share of its work), ``entry_arcs`` (the degrees of the
        active (row, vertex) entries: what an entry-level relax would
        touch), and per sweep ``entries_*`` (frontier entries over every
        relax) and ``reached_*`` (finite entries of T at the sweep's
        end; ``entries_bf / reached_bf`` is MFBF's re-entry, 1 on unit
        weights). Per-iteration counts come from the trace's
        ``TRACE_CAP`` slots, so a sweep longer than that adds only the
        iterations its slots hold.
        """
        return dict(self._occ) if self._occ else None

    def _traced(self, src, val, keep: int):
        """One traced batch: the first ``keep`` moments, pulled with both
        traces in one transfer; the traces go to the summary."""
        out = mfbc_batch_moments_traced(self._adj, jnp.asarray(src),
                                        jnp.asarray(val))
        *stats, tr_bf, tr_br = _pull(*out[:keep], *out[3:])
        self._record_occupancy(tr_bf, tr_br)
        return stats

    def _moments(self, src, val) -> Moments:
        if self._trace:
            s1, s2, nr = self._traced(src, val, 3)
        else:
            s1, s2, nr = _pull(*mfbc_batch_moments(
                self._adj, jnp.asarray(src), jnp.asarray(val)))
        return s1.astype(np.float64), s2.astype(np.float64), nr

    def _sum(self, src, val) -> np.ndarray:
        if self._trace:
            # S1 of the moments entry point IS λ_partial, so the exact
            # sweep can ride the traced path at the cost of one extra
            # elementwise square it discards.
            (s1,) = self._traced(src, val, 1)
        else:
            (s1,) = _pull(mfbc_batch(self._adj, jnp.asarray(src),
                                     jnp.asarray(val))[0])
        return s1.astype(np.float64)

    def _segmented(self, src, val, sid, n_seg: int, bucket: int) -> Moments:
        s1, s2, nr = _pull(*mfbc_batch_moments_segmented(
            self._adj, jnp.asarray(src), jnp.asarray(val), jnp.asarray(sid),
            n_slots=n_seg))
        return s1.astype(np.float64), s2.astype(np.float64), nr

    def _metric_moments(self, src, val, metric: str, hops: int) -> Moments:
        mids = jnp.zeros(src.shape[0], jnp.int32)
        s1, s2, nr = _pull(*metric_batch_moments(
            self._adj, jnp.asarray(src), jnp.asarray(val), mids,
            kinds=(metric,), hops=int(hops)))
        return s1.astype(np.float64), s2.astype(np.float64), nr

    def _metric_segmented(self, src, val, sid, mids, kinds, n_seg: int,
                          bucket: int, hops: int) -> Moments:
        s1, s2, nr = _pull(*metric_batch_moments_segmented(
            self._adj, jnp.asarray(src), jnp.asarray(val), jnp.asarray(sid),
            jnp.asarray(mids), kinds=kinds, n_slots=n_seg, hops=int(hops)))
        return s1.astype(np.float64), s2.astype(np.float64), nr

    def labels(self) -> np.ndarray:
        if self._cc_adj is None:
            self._cc_adj = backend_spec(self.plan.backend).make_adjacency(
                components_graph(self._g), self.plan)
        return np.asarray(components_labels(self._cc_adj), np.float64)


class MeshExecutor(_ExecutorBase):
    """Distributed Theorem 5.1 moments step on a (pod, data, model) mesh.

    ``mesh=None`` builds the mesh the plan chose (``plan.mesh_axes``) from
    the visible devices; pass an explicit mesh to reuse one. All variants
    and buckets share one lazily built ``MeshBCContext`` — the padded,
    permuted adjacency is uploaded once, and each (bucket, variant) pair
    compiles once.
    """

    def __init__(self, g: Graph, plan: BCPlan, mesh=None):
        if mesh is None:
            import jax

            axes = plan.axes_dict()
            if axes is None:
                raise ValueError("plan has no mesh_axes and no mesh given")
            mesh = jax.make_mesh(tuple(axes.values()), tuple(axes.keys()))
        self.plan = plan
        self.mesh = mesh
        self._g = g
        # Lazy context: an executor built for planning introspection never
        # pads or uploads the adjacency.
        self._ctx = None
        # MeshBCContext's batch rounding (sources are sharded over
        # pod×data), computed up front so callers can size sample
        # batches before any device work happens; _context asserts the
        # two stay in sync.
        sizes = dict(zip(mesh.axis_names, (int(s) for s in
                                           mesh.devices.shape)))
        chunk = sizes.get("pod", 1) * sizes.get("data", 1)
        self.n_b = -(-plan.n_b // chunk) * chunk
        # Bucket set: the plan's power-of-two shapes, each rounded up to
        # the mesh divisibility (dedup keeps them ascending).
        rounded = [-(-b // chunk) * chunk
                   for b in (plan.buckets or bucket_sizes(plan.n_b))]
        rounded.append(self.n_b)
        self.buckets = tuple(sorted({min(b, self.n_b) for b in rounded}))

    def _context(self):
        from repro.core.dist_bc import MeshBCContext

        if self._ctx is None:
            pl = self.plan
            self._ctx = MeshBCContext(self._g, self.mesh,
                                      iters=pl.iters if pl.iters > 0 else 0,
                                      use_kernel=pl.use_kernel,
                                      block=pl.block)
            assert self._ctx.round_nb(pl.n_b) == self.n_b, \
                (self._ctx.round_nb(pl.n_b), self.n_b)
        return self._ctx

    def _moments(self, src, val) -> Moments:
        return self._context().run_moments(src, val, nb=self.n_b)

    def _sum(self, src, val) -> np.ndarray:
        return self._context().run_sum(src, val, nb=self.n_b)

    def _segmented(self, src, val, sid, n_seg: int, bucket: int) -> Moments:
        return self._context().run_segmented(src, val, sid, n_seg, nb=bucket)


def build_executor(g: Graph, plan: BCPlan, *, mesh=None) -> BatchExecutor:
    """Instantiate the executor a ``BCPlan`` calls for.

    The plan's backend must be registered (``register_backend``) and
    must support the plan's placement — a mesh plan on a single-host-only
    backend is a planner bug surfaced here, not a silent fallback.
    """
    spec = backend_spec(plan.backend)
    if plan.placement == "mesh" or mesh is not None:
        if "mesh" not in spec.placements:
            raise ValueError(f"backend {spec.backend.value!r} has no mesh "
                             f"step (placements: {spec.placements})")
        return MeshExecutor(g, plan, mesh=mesh)
    return SingleHostExecutor(g, plan)
