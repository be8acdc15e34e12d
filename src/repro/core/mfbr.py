"""MFBr — Maximal Frontier Brandes back-propagation (paper Algorithm 2).

Given distances/multiplicities ``T = (Tw, Tm)`` from MFBF, computes the
partial centrality factors ``ζ(s, v) = δ(s, v) / σ̄(s, v)``.

We implement the Lemma 4.2 semantics with the counter mechanism:

* ``c0(s, v)`` = number of SP-DAG children of ``v`` (vertices ``u`` with
  ``τ(s,v) + A(v,u) = τ(s,u)``). The paper's Algorithm 2 lines 1–2 compute
  this with one ``•_(⊗,g)`` product; we use the equivalent one-shot count
  (see DESIGN.md §3 on the pseudocode's counter off-by-one).
* A vertex enters the frontier exactly once, when its counter hits zero
  (all children have reported), carrying ``1/σ̄(s,v) + ζ(s,v)``; it is then
  retired (paper's ``c = -1`` state → our ``done`` mask).
* Each round back-propagates the frontier with the centpath action
  ``g((w,p,c), a) = (w-a, p, c)`` and the ⊗ max-select: a predecessor ``v``
  accepts a contribution iff the shifted weight equals ``τ(s, v)`` exactly —
  i.e. the arc is on a shortest path — accumulating ``Σ_u (1/σ̄(s,u)+ζ(s,u))``
  and decrementing its counter by the number of children that reported.

The caller must mask the self-destination ``T(s, s̄(s)) = (∞, 1)`` first
(σ(s, t, v) with t = s is excluded from betweenness by definition).
"""
from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.adjacency import RelaxStats, no_compaction
from repro.core.mfbf import SweepTrace, count_finite, empty_trace
from repro.core.monoids import INF, Centpath


def _seed_frontier(Tw, Tm, Zp, newly):
    Fw = jnp.where(newly, Tw, -INF)
    Fp = jnp.where(newly, Zp + 1.0 / Tm, 0.0)
    return Centpath(Fw, Fp, jnp.where(newly, 1.0, 0.0))


def _relax_with_stats(adj, F: Centpath) -> Tuple[Centpath, RelaxStats]:
    fn = getattr(adj, "relax_cp_stats", None)
    if fn is None:
        return adj.relax_cp(F), no_compaction()
    return fn(F)


# Loop state: (Zp, c, done, F, |F|, SweepTrace).
State = Tuple[jax.Array, jax.Array, jax.Array, Centpath, jax.Array,
              SweepTrace]


def _step(adj, Tw, Tm, finite, state: State) -> State:
    """One back-prop round; ``|F|`` of the returned state is the
    population of the next frontier (vertices newly retired this round) —
    the while cond reads it instead of re-reducing ``F.c`` over (nb, n)."""
    Zp, c, done, F, nact, tr = state
    P, st = _relax_with_stats(adj, F)  # contributions shifted back along arcs
    with jax.named_scope("update"):
        contrib = (P.w == Tw) & finite & (P.c > 0)
        Zp = Zp + jnp.where(contrib, P.p, 0.0)
        c = c - jnp.where(contrib, P.c.astype(c.dtype), 0)
        newly = finite & (c == 0) & (~done)
        F = _seed_frontier(Tw, Tm, Zp, newly)
        done = done | newly
        return (Zp, c, done, F, jnp.sum(newly.astype(jnp.int32)),
                tr.record(nact, st))


@jax.named_scope("init")
def _init(adj, Tw: jax.Array, Tm: jax.Array):
    """(Tm_safe, finite, state0): the seed frontier is every reachable
    vertex with no SP-DAG child (paper Algorithm 2 lines 1–2)."""
    finite = jnp.isfinite(Tw)
    Tm_safe = jnp.where(Tm > 0, Tm, 1.0)  # the paper's (∞, 1) reciprocal guard
    c0 = adj.count_sp_children(Tw)
    Zp0 = jnp.zeros_like(Tw)
    seed = finite & (c0 == 0)
    return Tm_safe, finite, (Zp0, c0, seed,
                             _seed_frontier(Tw, Tm_safe, Zp0, seed),
                             jnp.sum(seed.astype(jnp.int32)), empty_trace())


def mfbr(adj, Tw: jax.Array, Tm: jax.Array, *,
         iterate: Union[str, Tuple[str, int]] = "while",
         max_iters: int = 0, trace: bool = False):
    """Back-propagate centrality factors. Returns ``Zp`` with
    ``Zp[s, v] = ζ(s, v)`` (0 for unreachable/masked vertices).
    With ``trace=True``: (Zp, SweepTrace) — see ``repro.core.mfbf``;
    the stages run under the scope ``mfbr`` as MFBF's run under ``mfbf``."""
    bound = max_iters if max_iters > 0 else adj.n - 1
    with jax.named_scope("mfbr"):
        Tm_safe, finite, state = _init(adj, Tw, Tm)

        def body(st):
            return _step(adj, Tw, Tm_safe, finite, st)

        if iterate == "while":
            state = jax.lax.while_loop(
                lambda st: (st[4] > 0) & (st[5].iters < bound), body, state)
        else:
            state = jax.lax.fori_loop(0, bound, lambda _, st: body(st), state)
        # the entries the sweep retires: every finite entry of T
        Zp, tr = state[0], state[5]._replace(reached=count_finite(Tw))
    return (Zp, tr) if trace else Zp
