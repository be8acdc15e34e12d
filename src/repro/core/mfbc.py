"""MFBC — combined betweenness centrality driver (paper Algorithm 3).

``λ(v) = Σ_s ζ(s, v) · σ̄(s, v)`` accumulated over ``⌈n / n_b⌉`` source
batches. The per-batch computation is a single jitted function; the batch
loop runs on the host, which is also where fault tolerance lives — the λ
accumulator plus the batch index *is* the checkpoint (see
``repro.launch.checkpoint``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mfbf as _mfbf
from repro.core import mfbr as _mfbr
from repro.core.adjacency import (CooAdj, CsrAdj, DenseAdj,
                                  coo_adj_from_graph, csr_adj_from_graph,
                                  dense_adj_from_graph)
from repro.core.monoids import INF
from repro.graphs.formats import Graph


def _batch_contrib(adj, sources: jax.Array, valid: jax.Array, *,
                   iterate: str, max_iters_bf: int, max_iters_br: int):
    """Shared Algorithm 3 batch body: per-source contributions δ_s(v).

    Returns (contrib, mask, Tw, Tm, tr_bf, tr_br) with contrib (nb, n)
    zeroed on unreachable/padding entries and the ``SweepTrace`` of each
    sweep (callers that do not return them leave them to dead-code
    removal).
    """
    nb = sources.shape[0]
    Tw, Tm, tr_bf = _mfbf.mfbf(adj, sources, iterate=iterate,
                               max_iters=max_iters_bf, trace=True)
    with jax.named_scope("batch.mask"):
        # Exclude the t = s destination (σ(s, t, v) = 0 when t = s): mask
        # the source's own column to (∞, 1) — the 1 keeps reciprocals safe.
        rows = jnp.arange(nb)
        Tw = Tw.at[rows, sources].set(INF)
        Tm = Tm.at[rows, sources].set(1.0)
    Zp, tr_br = _mfbr.mfbr(adj, Tw, Tm, iterate=iterate,
                           max_iters=max_iters_br, trace=True)
    with jax.named_scope("batch.reduce"):
        mask = jnp.isfinite(Tw) & valid[:, None]
        contrib = jnp.where(mask, Zp * Tm, 0.0)
    return contrib, mask, Tw, Tm, tr_bf, tr_br


@jax.named_scope("batch.reduce")
def _moments(contrib: jax.Array, mask: jax.Array):
    return (jnp.sum(contrib, axis=0), jnp.sum(contrib * contrib, axis=0),
            jnp.sum(mask, axis=0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("iterate", "max_iters_bf",
                                             "max_iters_br"))
def mfbc_batch(adj, sources: jax.Array, valid: jax.Array, *,
               iterate: str = "while", max_iters_bf: int = 0,
               max_iters_br: int = 0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One batch of Algorithm 3: returns (λ_partial, Tw, Tm).

    valid: (nb,) bool — False for padding sources (contribute nothing).
    """
    contrib, _, Tw, Tm, _, _ = _batch_contrib(
        adj, sources, valid, iterate=iterate, max_iters_bf=max_iters_bf,
        max_iters_br=max_iters_br)
    with jax.named_scope("batch.reduce"):
        return jnp.sum(contrib, axis=0), Tw, Tm


@functools.partial(jax.jit, static_argnames=("iterate", "max_iters_bf",
                                             "max_iters_br"))
def mfbc_batch_moments(adj, sources: jax.Array, valid: jax.Array, *,
                       iterate: str = "while", max_iters_bf: int = 0,
                       max_iters_br: int = 0
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One Algorithm 3 batch returning per-vertex dependency moments.

    Returns (S1, S2, n_reach) where, over the batch's valid sources s,
    ``S1(v) = Σ_s δ_s(v)``, ``S2(v) = Σ_s δ_s(v)²`` and
    ``n_reach(v) = Σ_s [v reachable from s]``. S1 equals ``mfbc_batch``'s
    λ_partial; S2 feeds the empirical-Bernstein confidence intervals of the
    adaptive approximate-BC estimator (``repro.approx``), which need the
    second moment per *source sample*, not the batch sum.
    """
    contrib, mask, _, _, _, _ = _batch_contrib(
        adj, sources, valid, iterate=iterate, max_iters_bf=max_iters_bf,
        max_iters_br=max_iters_br)
    return _moments(contrib, mask)


@functools.partial(jax.jit, static_argnames=("max_iters_bf", "max_iters_br"))
def mfbc_batch_moments_traced(adj, sources: jax.Array, valid: jax.Array, *,
                              max_iters_bf: int = 0, max_iters_br: int = 0):
    """``mfbc_batch_moments`` plus the per-iteration occupancy traces.

    Returns (S1, S2, n_reach, trace_bf, trace_br) where the traces are
    ``repro.core.mfbf.SweepTrace`` tuples for the forward (MFBF) and
    backward (MFBr) sweeps of this batch. Both entry points run the same
    loop bodies, which always carry the traces; this one returns them,
    so the moments are bitwise-unchanged.
    """
    contrib, mask, _, _, tr_bf, tr_br = _batch_contrib(
        adj, sources, valid, iterate="while", max_iters_bf=max_iters_bf,
        max_iters_br=max_iters_br)
    return (*_moments(contrib, mask), tr_bf, tr_br)


@functools.partial(jax.jit, static_argnames=("n_slots", "iterate",
                                             "max_iters_bf", "max_iters_br"))
def mfbc_batch_moments_segmented(adj, sources: jax.Array, valid: jax.Array,
                                 slot_ids: jax.Array, *, n_slots: int,
                                 iterate: str = "while",
                                 max_iters_bf: int = 0, max_iters_br: int = 0
                                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One Algorithm 3 batch, moments segment-reduced per request slot.

    The cross-request fusion primitive: a fused batch packs sources from
    several concurrent queries, tagged per row with ``slot_ids[s] ∈
    [0, n_slots)`` (padding rows carry ``slot_ids == n_slots``, a dump
    segment that is dropped). Returns (S1, S2, n_reach) each shaped
    ``(n_slots, n)``, where row j holds exactly what
    ``mfbc_batch_moments`` would return for slot j's rows alone — the
    segment-sum accumulates each slot's rows in batch order, so a slot's
    statistics are bitwise-identical to an unfused run of the same rows.
    One device call (and, on the mesh analogue, one fused all-reduce)
    therefore serves every query in the batch.
    """
    contrib, mask, _, _, _, _ = _batch_contrib(
        adj, sources, valid, iterate=iterate, max_iters_bf=max_iters_bf,
        max_iters_br=max_iters_br)
    seg = functools.partial(jax.ops.segment_sum, segment_ids=slot_ids,
                            num_segments=n_slots + 1)
    with jax.named_scope("batch.reduce"):
        return (seg(contrib)[:n_slots], seg(contrib * contrib)[:n_slots],
                seg(mask.astype(jnp.int32))[:n_slots])


# ==========================================================================
# Metric-generic batch bodies (the MetricSpec sweep substrate).
#
# Every sampled metric shares MFBF's forward sweep and the t = s self-mask;
# they differ only in the final elementwise contribution formula (and, for
# betweenness, the extra MFBr backward sweep). ``kinds`` is the *static*
# tuple of metric names present in the batch and ``metric_ids`` tags each
# row with an index into it, so a fused batch mixes metrics row-wise while
# the relax sequence stays one shared collective. With
# ``kinds=("betweenness",)`` the computation is the same op sequence as
# ``mfbc_batch_moments`` — the generic entry points never perturb the
# default path, which keeps calling the original functions above.
# ==========================================================================


def _bounded_mfbf(adj, sources: jax.Array, *, hops: int):
    """MFBF stopped after ``hops - 1`` iterations (Lemma 4.1: T is then
    exactly the ≤ ``hops``-edge shortest paths; finiteness is hop-bounded
    reachability). ``hops=1`` runs zero iterations — T is the direct-edge
    row gather itself."""
    with jax.named_scope("mfbf"):
        T, _, _, _ = jax.lax.fori_loop(
            0, hops - 1, lambda _, s: _mfbf._step(adj, s),
            _mfbf._init(adj, sources))
    return T.w, T.m


def _metric_contrib(adj, sources: jax.Array, valid: jax.Array,
                    metric_ids: jax.Array, *, kinds, hops: int,
                    iterate: str, max_iters_bf: int, max_iters_br: int):
    """Metric-generic Algorithm 3 batch body: (contrib, mask).

    kinds: static tuple of metric names; rows select theirs via
    ``metric_ids``. Bounded (khop) and unbounded sweeps never mix — the
    serving layer groups fusion by ``core.metrics.fuse_group``.
    """
    nb = sources.shape[0]
    bounded = any(k == "khop" for k in kinds)
    if bounded:
        if not all(k == "khop" for k in kinds):
            raise ValueError("hop-bounded sweeps cannot fuse with "
                             f"unbounded metrics: {kinds}")
        if hops < 1:
            raise ValueError(f"khop requires hops >= 1, got {hops}")
        Tw, Tm = _bounded_mfbf(adj, sources, hops=hops)
    else:
        Tw, Tm = _mfbf.mfbf(adj, sources, iterate=iterate,
                            max_iters=max_iters_bf)
    rows = jnp.arange(nb)
    Tw = Tw.at[rows, sources].set(INF)
    Tm = Tm.at[rows, sources].set(1.0)
    mask = jnp.isfinite(Tw) & valid[:, None]
    Zp = None
    if any(k == "betweenness" for k in kinds):
        Zp = _mfbr.mfbr(adj, Tw, Tm, iterate=iterate, max_iters=max_iters_br)

    def one(kind):
        if kind == "betweenness":
            return Zp * Tm
        if kind == "closeness":
            return Tw  # farness: δ_s(v) = τ(s, v) where finite
        if kind == "khop":
            return jnp.ones_like(Tw)  # reach indicator within the bound
        raise ValueError(f"metric {kind!r} has no sampled batch body")

    contrib = one(kinds[0])
    for i, kind in enumerate(kinds[1:], start=1):
        contrib = jnp.where((metric_ids == i)[:, None], one(kind), contrib)
    return jnp.where(mask, contrib, 0.0), mask


@functools.partial(jax.jit, static_argnames=("kinds", "hops", "iterate",
                                             "max_iters_bf", "max_iters_br"))
def metric_batch_moments(adj, sources: jax.Array, valid: jax.Array,
                         metric_ids: jax.Array, *, kinds, hops: int = 0,
                         iterate: str = "while", max_iters_bf: int = 0,
                         max_iters_br: int = 0
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``mfbc_batch_moments`` generalized over per-row metrics.

    Returns (S1, S2, n_reach) over the batch's valid sources, where each
    row's contribution formula is selected by ``kinds[metric_ids[row]]``.
    """
    contrib, mask = _metric_contrib(adj, sources, valid, metric_ids,
                                    kinds=kinds, hops=hops, iterate=iterate,
                                    max_iters_bf=max_iters_bf,
                                    max_iters_br=max_iters_br)
    return (jnp.sum(contrib, axis=0), jnp.sum(contrib * contrib, axis=0),
            jnp.sum(mask, axis=0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("kinds", "hops", "n_slots",
                                             "iterate", "max_iters_bf",
                                             "max_iters_br"))
def metric_batch_moments_segmented(adj, sources: jax.Array,
                                   valid: jax.Array, slot_ids: jax.Array,
                                   metric_ids: jax.Array, *, kinds,
                                   n_slots: int, hops: int = 0,
                                   iterate: str = "while",
                                   max_iters_bf: int = 0,
                                   max_iters_br: int = 0
                                   ) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """``mfbc_batch_moments_segmented`` generalized over per-row metrics.

    The cross-metric fusion primitive: a closeness epoch and a BC forward
    sweep share one relax collective, with each slot's rows selecting
    their own contribution formula. Per-slot segment sums accumulate each
    slot's rows in batch order, so slot j's statistics stay
    bitwise-identical to an unfused run of the same rows under the same
    ``kinds``-compatible sweep structure.
    """
    contrib, mask = _metric_contrib(adj, sources, valid, metric_ids,
                                    kinds=kinds, hops=hops, iterate=iterate,
                                    max_iters_bf=max_iters_bf,
                                    max_iters_br=max_iters_br)
    seg = functools.partial(jax.ops.segment_sum, segment_ids=slot_ids,
                            num_segments=n_slots + 1)
    return (seg(contrib)[:n_slots], seg(contrib * contrib)[:n_slots],
            seg(mask.astype(jnp.int32))[:n_slots])


def mfbc(g: Graph, *, n_b: Optional[int] = None, backend: str = "dense",
         iterate: str = "while", max_iters: int = 0, block: int = 512,
         use_kernel: bool = False, sources: Optional[np.ndarray] = None,
         progress_cb=None, execution=None) -> np.ndarray:
    """Full betweenness centrality of a host graph.

    Args:
      g: host COO graph (positive weights).
      n_b: batch size (paper's memory/time tradeoff). Default min(n, 64).
      backend: "dense" (blocked tropical matmul / Pallas), "coo"
        (segment-op message passing) or "csr" (frontier-compacted
        segment-op message passing).
      iterate: "while" | "fori" (static bound, for cost analysis).
      max_iters: static iteration bound for "fori" (default n-1).
      sources: optionally restrict to these sources (approximate BC).
      progress_cb: optional callback(batch_idx, n_batches, lam_partial)
        — the checkpoint hook.
      execution: optional backend-dispatch config overriding ``backend``/
        ``block``/``use_kernel``. Duck-typed (anything with those three
        attributes, e.g. ``repro.bc.ExecutionConfig``) so the core layer
        never imports the solver facade — ``repro.bc`` imports core, not
        the reverse.

    Returns:
      λ: (n,) float64 centrality scores (ordered-pair convention, endpoints
      excluded — matches the paper's λ definition).
    """
    n = g.n
    if n_b is None:
        n_b = min(n, 64)
    if execution is not None:
        if execution.backend is not None:
            backend = str(getattr(execution.backend, "value",
                                  execution.backend))
        if execution.use_kernel is not None:
            use_kernel = bool(execution.use_kernel)
        block = int(execution.block)
    if backend == "dense":
        adj = dense_adj_from_graph(g, block=block, use_kernel=use_kernel)
    elif backend == "coo":
        adj = coo_adj_from_graph(g)
    elif backend == "csr":
        adj = csr_adj_from_graph(g, n_b=n_b)
    else:
        raise ValueError(f"unknown backend {backend!r}")

    all_sources = np.arange(n, dtype=np.int32) if sources is None \
        else np.asarray(sources, dtype=np.int32)
    n_src = all_sources.shape[0]
    n_batches = -(-n_src // n_b)
    lam = np.zeros(n, dtype=np.float64)
    for b in range(n_batches):
        chunk = all_sources[b * n_b:(b + 1) * n_b]
        valid = np.ones(chunk.shape[0], dtype=bool)
        if chunk.shape[0] < n_b:  # pad the ragged tail (paper's n mod n_b trick)
            pad = n_b - chunk.shape[0]
            chunk = np.concatenate([chunk, np.zeros(pad, np.int32)])
            valid = np.concatenate([valid, np.zeros(pad, bool)])
        lam_b, _, _ = mfbc_batch(adj, jnp.asarray(chunk), jnp.asarray(valid),
                                 iterate=iterate, max_iters_bf=max_iters,
                                 max_iters_br=max_iters)
        lam += np.asarray(lam_b, dtype=np.float64)
        if progress_cb is not None:
            progress_cb(b, n_batches, lam)
    return lam
