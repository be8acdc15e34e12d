"""MFBF — Maximal Frontier Bellman-Ford (paper Algorithm 1, Lemma 4.1).

Computes, for a batch of ``n_b`` sources, the shortest distance ``τ(s, v)``
and the shortest-path multiplicity ``σ̄(s, v)`` for every vertex ``v``.

Loop invariant (the Lemma 4.1 induction): after ``j`` iterations

* ``T``  holds weight/multiplicity of all shortest paths of **≤ j+1** edges,
* the frontier ``F`` holds weight/multiplicity of minimal-weight paths of
  **exactly j+1** edges that tie the current best (everything that can still
  make progress — the *maximal* frontier).

The paper's ``(∞, 1)`` initialisation trick is kept implicitly: inactive
entries are ``(∞, 0)`` in the frontier (so they are never relaxed — CTF
keeps them structurally absent), while ``T``'s multiplicity for unreachable
vertices is clamped to 1 just before reciprocals are taken in MFBr.

``iterate`` selects ``lax.while_loop`` (dynamic trip count — production) or
``lax.fori_loop`` with a static bound (used by the dry-run/roofline so that
``cost_analysis`` sees the real per-iteration work).

The while-loop condition reads an active count folded into the loop carry:
``_step`` computes the next frontier's population from the ``keep`` mask it
already materializes, so the cond never re-reduces the full ``(n_b, n)``
frontier. ``F'`` is active exactly where ``keep`` holds, so the carried
count is identical to ``jnp.any(_frontier_active(F'))`` and results are
bitwise-unchanged.

Every sweep threads a :class:`SweepTrace` through its one loop body;
``trace=True`` only returns it. Per iteration it records the frontier
nnz and, for adjacencies with frontier compaction (``CsrAdj``), the rung
that served the relax, the arcs leaving the union frontier and the arcs
of the active entries themselves; per sweep the relax calls a rung
served, those that overflowed to the full edge list, the arcs needed
against the arc slots processed, and the entries of ``T`` reached. On a
weighted graph an entry can join the frontier again each time a path of
more edges lowers its distance, so the frontier nnz summed over a sweep
over its ``reached`` is the sweep's label-correcting re-entry (1 on
unit weights).

Each stage runs under a ``jax.named_scope`` (``mfbf`` → ``init``, the
relax scopes of ``repro.core.adjacency``, ``update``), so a profiler
trace's ``tf_op`` names where every device op came from. Scopes are
metadata: they leave the computation unchanged.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.adjacency import RelaxStats, no_compaction
from repro.core.monoids import INF, Multpath, multpath_combine

# Fixed-size per-iteration occupancy trace; iterations past the cap fold
# into the last slot (so ``fnnz[min(iters, cap) - 1]`` is always the tail).
TRACE_CAP = 64


class SweepTrace(NamedTuple):
    """Occupancy side-channel of one frontier sweep (MFBF or MFBr).

    Per-iteration slots hold -1 where unused; ``bucket`` is -1 on an
    adjacency with no capacity ladder, ``len(caps)`` on the full-edge-list
    fallback. ``frontier_arcs``, ``arc_slots`` and ``entry_arcs`` are 0
    without a ladder. ``entry_arcs`` stays per iteration so that each
    int32 holds at most ``n_b · E`` (see ``RelaxStats``); the host sums
    it in Python ints. ``reached`` is set once, after the loop.
    """

    fnnz: jax.Array  # (TRACE_CAP,) int32 frontier nnz entering each relax
    bucket: jax.Array  # (TRACE_CAP,) int32 ladder rung of each relax
    arcs: jax.Array  # (TRACE_CAP,) int32 arcs leaving the union frontier
    iters: jax.Array  # int32 — iterations executed
    overflows: jax.Array  # int32 — relax calls on the full-edge-list fallback
    compact_hits: jax.Array  # int32 — relax calls served by a capacity bucket
    frontier_arcs: jax.Array  # int32 — Σ arcs over every iteration
    arc_slots: jax.Array  # int32 — Σ arc slots the chosen branches processed
    entry_arcs: jax.Array  # (TRACE_CAP,) int32 arcs of the active entries
    reached: jax.Array  # int32 — finite entries of T when the sweep ends

    def record(self, nact: jax.Array, st: RelaxStats) -> "SweepTrace":
        """The trace after one more relax, which saw ``nact`` entries."""
        slot = jnp.minimum(self.iters, TRACE_CAP - 1)
        hit = ((st.bucket >= 0) & (st.overflow == 0)).astype(jnp.int32)
        return SweepTrace(self.fnnz.at[slot].set(nact),
                          self.bucket.at[slot].set(st.bucket),
                          self.arcs.at[slot].set(st.arcs), self.iters + 1,
                          self.overflows + st.overflow,
                          self.compact_hits + hit,
                          self.frontier_arcs + st.arcs,
                          self.arc_slots + st.slots,
                          self.entry_arcs.at[slot].set(st.entry_arcs),
                          self.reached)


def empty_trace() -> SweepTrace:
    unused = jnp.full((TRACE_CAP,), -1, jnp.int32)
    zero = jnp.int32(0)
    return SweepTrace(unused, unused, unused, zero, zero, zero, zero, zero,
                      unused, zero)


def count_finite(Tw: jax.Array) -> jax.Array:
    """int32 count of the finite entries of ``Tw`` (a sweep's reach)."""
    return jnp.sum(jnp.isfinite(Tw), dtype=jnp.int32)


def _frontier_active(F: Multpath) -> jax.Array:
    return jnp.isfinite(F.w) & (F.m > 0)


def _relax_with_stats(adj, F: Multpath) -> Tuple[Multpath, RelaxStats]:
    fn = getattr(adj, "relax_mp_stats", None)
    if fn is None:
        return adj.relax_mp(F), no_compaction()
    return fn(F)


# Loop state: (T, F, |F active|, SweepTrace).
State = Tuple[Multpath, Multpath, jax.Array, SweepTrace]


@jax.named_scope("init")
def _init(adj, sources: jax.Array) -> State:
    Tw0 = adj.gather_rows(sources)  # direct edges, (nb, n); paper line 1
    Tm0 = jnp.where(jnp.isfinite(Tw0), 1.0, 0.0).astype(Tw0.dtype)
    T0 = Multpath(Tw0, Tm0)
    # paper line 2: initial frontier = exactly-1-edge paths
    nact0 = jnp.sum(_frontier_active(T0).astype(jnp.int32))
    return T0, T0, nact0, empty_trace()


def _step(adj, state: State) -> State:
    """One maximal-frontier relaxation: (T', F', |F' active|, trace')."""
    T, F, nact, tr = state
    C, st = _relax_with_stats(adj, F)  # exactly-(j+1)-edge minimal paths
    with jax.named_scope("update"):
        T_new = multpath_combine(T, C)
        # New frontier: candidates that match the (possibly improved) best
        # distance. Exactly-j-edge path classes are disjoint, so
        # multiplicities accumulate without double counting.
        keep = (C.w == T_new.w) & jnp.isfinite(C.w) & (C.m > 0)
        F_new = Multpath(jnp.where(keep, C.w, INF),
                         jnp.where(keep, C.m, 0.0))
        return (T_new, F_new, jnp.sum(keep.astype(jnp.int32)),
                tr.record(nact, st))


def mfbf(adj, sources: jax.Array, *,
         iterate: Union[str, Tuple[str, int]] = "while",
         max_iters: int = 0, trace: bool = False):
    """Run MFBF for one batch of sources.

    Args:
      adj: DenseAdj, CooAdj or CsrAdj.
      sources: (nb,) int32 vertex ids.
      iterate: "while" for a dynamic loop, "fori" for a static loop of
        ``max_iters`` iterations (must upper-bound the SP edge count).
      max_iters: static bound; also caps the while loop defensively
        (0 means n - 1).
      trace: also return the :class:`SweepTrace` occupancy side output.

    Returns:
      (Tw, Tm): (nb, n) distances and multiplicities. Unreachable = (inf, 0).
      With ``trace=True``: (Tw, Tm, SweepTrace).
    """
    bound = max_iters if max_iters > 0 else adj.n - 1
    with jax.named_scope("mfbf"):
        state = _init(adj, sources)
        if iterate == "while":
            state = jax.lax.while_loop(
                lambda s: (s[2] > 0) & (s[3].iters < bound),
                lambda s: _step(adj, s), state)
        else:
            state = jax.lax.fori_loop(0, bound, lambda _, s: _step(adj, s),
                                      state)
        T, _, _, tr = state
        tr = tr._replace(reached=count_finite(T.w))
    return (T.w, T.m, tr) if trace else (T.w, T.m)
