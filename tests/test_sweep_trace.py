"""The sweeps' occupancy counters and named scopes.

Every MFBF/MFBr loop body threads a ``SweepTrace``: per iteration the
frontier nnz, the capacity rung that served the relax, the arcs
leaving the union frontier and the arcs of the active entries; per
sweep the arc slots the chosen branches processed and the entries of T
reached. Here those counts are recounted on the host from the frontier
masks, step by step, and the named scopes and the counters are shown
to change nothing but metadata and side outputs.
"""
import importlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adjacency import csr_adj_from_graph
from repro.core.mfbf import TRACE_CAP
from repro.core.monoids import INF
from repro.graphs.generators import rmat

# the modules (``repro.core`` re-exports functions of the same names)
F = importlib.import_module("repro.core.mfbf")
R = importlib.import_module("repro.core.mfbr")
NB = 4


@pytest.fixture(scope="module")
def graph():
    g, _ = rmat(6, 4, seed=3, weighted=True, max_weight=4).remove_isolated()
    return g


def _sources(g):
    return jnp.asarray(np.random.default_rng(0).choice(g.n, NB,
                                                       replace=False),
                       jnp.int32)


def _masks(step, state, nact_at):
    """Frontier masks entering each relax, by running the loop body one
    iteration at a time until the frontier empties."""
    masks = []
    while int(state[nact_at]) > 0:
        masks.append(np.isfinite(np.asarray(state[nact_at - 1].w)))
        state = step(state)
    return masks


def _recount(masks, indptr, caps, n_arcs, rungs=None):
    """(fnnz, rung, arcs, slots, entry_arcs) of each relax, from its
    frontier mask; the relax switches over the first ``rungs`` of
    ``caps`` (all of them by default)."""
    indptr = np.asarray(indptr)
    deg = indptr[1:] - indptr[:-1]
    kept = caps[:rungs]
    rows = []
    for m in masks:
        cols = m.any(axis=0)
        nnz, arcs = int(cols.sum()), int(deg[cols].sum())
        rung = next((i for i, (v, e) in enumerate(kept)
                     if nnz <= v and arcs <= e), len(caps))
        slots = caps[rung][1] if rung < len(caps) else n_arcs
        rows.append((int(m.sum()), rung, arcs, slots,
                     int((m * deg[None, :]).sum())))
    return rows


def _check(tr, rows, caps):
    k = int(tr.iters)
    assert k == len(rows) and k < TRACE_CAP
    got = list(zip(np.asarray(tr.fnnz)[:k].tolist(),
                   np.asarray(tr.bucket)[:k].tolist(),
                   np.asarray(tr.arcs)[:k].tolist()))
    assert got == [r[:3] for r in rows]
    assert np.all(np.asarray(tr.bucket)[k:] == -1)
    assert int(tr.frontier_arcs) == sum(r[2] for r in rows)
    assert int(tr.arc_slots) == sum(r[3] for r in rows)
    assert int(tr.frontier_arcs) <= int(tr.arc_slots)
    assert int(tr.overflows) == sum(r[1] == len(caps) for r in rows)
    assert int(tr.compact_hits) == k - int(tr.overflows)


@pytest.mark.parametrize("caps", [((1, 1),), ((64, 32), (64, 256)), None],
                         ids=["overflow", "two-rung", "default"])
def test_trace_counts_match_a_host_recount(graph, caps):
    g = graph
    adj = csr_adj_from_graph(g, n_b=NB, caps=caps)
    caps, n_arcs = adj.caps, int(adj.src.shape[0])
    src = _sources(g)

    Tw, Tm, tr_bf = F.mfbf(adj, src, trace=True)
    step_bf = jax.jit(lambda s: F._step(adj, s))
    rows_bf = _recount(_masks(step_bf, F._init(adj, src), 2), adj.indptr,
                       caps, n_arcs)
    _check(tr_bf, rows_bf, caps)

    rows = jnp.arange(NB)
    Tw = Tw.at[rows, src].set(INF)
    Tm = Tm.at[rows, src].set(1.0)
    _, tr_br = R.mfbr(adj, Tw, Tm, trace=True)
    Tm_safe, finite, state = R._init(adj, Tw, Tm)
    step_br = jax.jit(lambda s: R._step(adj, Tw, Tm_safe, finite, s))
    rows_br = _recount(_masks(step_br, state, 4), adj.indptr_in, caps,
                       n_arcs, adj.cp_rungs)
    _check(tr_br, rows_br, caps)
    if caps == ((1, 1),):
        # a frontier of one column with one arc still fits the lone rung
        assert any(r[1] == 1 for r in rows_bf + rows_br)


@pytest.mark.parametrize("caps", [((1, 1),), ((64, 32), (64, 256)), None],
                         ids=["overflow", "two-rung", "default"])
def test_entry_arcs_and_reached_match_a_host_recount(graph, caps):
    """Per iteration, the degrees of the active (row, vertex) entries;
    per sweep, the finite entries of T: both recounted from the frontier
    masks and the sweep's outputs on a weighted graph."""
    g = graph
    adj = csr_adj_from_graph(g, n_b=NB, caps=caps)
    caps, n_arcs = adj.caps, int(adj.src.shape[0])
    src = _sources(g)

    Tw, Tm, tr_bf = F.mfbf(adj, src, trace=True)
    step_bf = jax.jit(lambda s: F._step(adj, s))
    rows_bf = _recount(_masks(step_bf, F._init(adj, src), 2), adj.indptr,
                       caps, n_arcs)
    k = int(tr_bf.iters)
    assert np.asarray(tr_bf.entry_arcs)[:k].tolist() == [r[4] for r in
                                                         rows_bf]
    assert np.all(np.asarray(tr_bf.entry_arcs)[k:] == -1)
    assert int(tr_bf.reached) == int(np.isfinite(np.asarray(Tw)).sum())
    # an entry is reached once and joins the frontier at least once
    assert sum(r[0] for r in rows_bf) >= int(tr_bf.reached) > 0

    rows = jnp.arange(NB)
    Tw = Tw.at[rows, src].set(INF)
    Tm = Tm.at[rows, src].set(1.0)
    _, tr_br = R.mfbr(adj, Tw, Tm, trace=True)
    Tm_safe, finite, state = R._init(adj, Tw, Tm)
    step_br = jax.jit(lambda s: R._step(adj, Tw, Tm_safe, finite, s))
    rows_br = _recount(_masks(step_br, state, 4), adj.indptr_in, caps,
                       n_arcs, adj.cp_rungs)
    k = int(tr_br.iters)
    assert np.asarray(tr_br.entry_arcs)[:k].tolist() == [r[4] for r in
                                                         rows_br]
    # the backward sweep retires every finite entry exactly once
    assert int(tr_br.reached) == int(np.isfinite(np.asarray(Tw)).sum())
    assert sum(r[0] for r in rows_br) == int(tr_br.reached)


def _path_graph(n):
    from repro.graphs.formats import Graph

    a = np.arange(n - 1, dtype=np.int32)
    return Graph(n, np.concatenate([a, a + 1]), np.concatenate([a + 1, a]),
                 np.ones(2 * (n - 1), np.float32), directed=False)


@pytest.mark.parametrize("caps", [((1, 1),), None],
                         ids=["overflow", "default"])
def test_unit_weights_enter_each_entry_once(caps):
    """On an unweighted path every reached (row, vertex) entry joins each
    sweep's frontier exactly once, with its own degree: re-entry 1.0 and
    entry arcs the reached entries' degrees summed."""
    from repro.bc import BCQuery, ExecutionConfig, build_executor, plan

    g = _path_graph(23)
    pl = plan(g, BCQuery(mode="exact", n_b=NB,
                         execution=ExecutionConfig(backend="csr")),
              n_devices=1)
    ex = build_executor(g, pl)
    if caps is not None:
        ex._adj = csr_adj_from_graph(g, n_b=NB, caps=caps)
    src = np.array([0, 5, 11, 22], np.int32)
    ex.step_sum(src, np.ones(NB, bool))
    occ = ex.occupancy_summary()
    assert occ["entries_bf"] == occ["reached_bf"]
    assert occ["entries_br"] == occ["reached_br"]
    Tw, _, _ = jax.device_get(F.mfbf(ex._adj, jnp.asarray(src), trace=True))
    deg = np.diff(np.asarray(ex._adj.indptr))  # in-degree equals out-degree
    fin = np.isfinite(Tw)
    assert occ["reached_bf"] == int(fin.sum())
    assert sum(r[3] for r in occ["rows_bf"]) == int((fin * deg).sum())
    fin[np.arange(NB), src] = False  # MFBr sweeps the self-masked T
    assert occ["reached_br"] == int(fin.sum())
    assert sum(r[3] for r in occ["rows_br"]) == int((fin * deg).sum())
    assert occ["entry_arcs"] == int((np.isfinite(Tw) * deg).sum()
                                    + (fin * deg).sum())


def _pick_without_counts(self, Fw, indptr, caps):
    """``CsrAdj._pick_bucket`` before the entry counts: the union columns
    from an ``any`` and no ``entry_arcs``."""
    deg = indptr[1:] - indptr[:-1]
    colmask = jnp.any(jnp.isfinite(Fw), axis=0)
    nnz = jnp.sum(colmask.astype(jnp.int32))
    arcs = jnp.sum(jnp.where(colmask, deg, 0)).astype(jnp.int32)
    bucket = jnp.int32(len(self.caps))
    for i in reversed(range(len(caps))):
        vcap, ecap = caps[i]
        bucket = jnp.where((nnz <= vcap) & (arcs <= ecap), jnp.int32(i),
                           bucket)
    slots = jnp.asarray([e for _, e in self.caps] + [self.src.shape[0]],
                        jnp.int32)[bucket]
    return nnz, arcs, bucket, slots, jnp.int32(0)


def test_entry_counters_leave_lambda_bitwise_equal(graph, monkeypatch):
    """The programs with the entry counters against the same programs
    with them taken out (the union columns from an ``any``, ``reached``
    never counted): λ and both moments are bitwise equal on every
    backend, and only the counters differ."""
    A = importlib.import_module("repro.core.adjacency")
    C = importlib.import_module("repro.core.mfbc")
    g = graph
    src = jnp.arange(4, dtype=jnp.int32) * 7
    val = jnp.array([True, True, True, False])
    adjs = (A.csr_adj_from_graph(g, n_b=4),
            A.csr_adj_from_graph(g, caps=((1, 1),)),
            A.csr_adj_from_graph(g, caps=((64, 32), (64, 256))),
            A.coo_adj_from_graph(g), A.dense_adj_from_graph(g, block=32))

    def run():
        out, reached = [], []
        for adj in adjs:
            out.append(np.asarray(C.mfbc_batch(adj, src, val)[0]))
            out.extend(np.asarray(x) for x in
                       C.mfbc_batch_moments(adj, src, val))
            *mom, tr_bf, tr_br = C.mfbc_batch_moments_traced(adj, src, val)
            out.extend(np.asarray(x) for x in mom)
            reached.append((int(tr_bf.reached), int(tr_br.reached),
                            int(np.asarray(tr_bf.entry_arcs).max())))
        return out, reached

    counted, reached = run()
    monkeypatch.setattr(A.CsrAdj, "_pick_bucket", _pick_without_counts)
    monkeypatch.setattr(F, "count_finite", lambda Tw: jnp.int32(0))
    monkeypatch.setattr(R, "count_finite", lambda Tw: jnp.int32(0))
    jax.clear_caches()
    try:
        plain, none = run()
    finally:
        jax.clear_caches()
    assert all(r[0] > 0 and r[1] > 0 for r in reached)
    assert all(r[2] > 0 for r in reached[:3])
    assert all(r == (0, 0, 0) for r in none[:3])
    for a, b in zip(counted, plain):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_executor_occupancy_sums_both_sweeps(graph):
    from repro.bc import BCQuery, ExecutionConfig, build_executor, plan

    g = graph
    pl = plan(g, BCQuery(mode="exact", n_b=NB,
                         execution=ExecutionConfig(backend="csr")),
              n_devices=1)
    ex = build_executor(g, pl)
    src = np.asarray(_sources(g))
    lam = ex.step_sum(src, np.ones(NB, bool))
    occ = ex.occupancy_summary()
    _, _, _, tr_bf, tr_br = jax.device_get(_traced_batch(ex._adj, src))
    assert occ["rows_bf"] == [
        [int(a), int(b), int(c), int(d)] for a, b, c, d in
        zip(tr_bf.fnnz, tr_bf.bucket, tr_bf.arcs,
            tr_bf.entry_arcs)][:int(tr_bf.iters)]
    assert occ["per_iter_br"] == [r[0] for r in occ["rows_br"]]
    assert occ["frontier_arcs"] == int(tr_bf.frontier_arcs) + int(
        tr_br.frontier_arcs)
    assert occ["arc_slots"] == int(tr_bf.arc_slots) + int(tr_br.arc_slots)
    assert 0 < occ["frontier_arcs"] <= occ["arc_slots"]
    assert all(isinstance(occ[k], int) for k in
               ("frontier_arcs", "arc_slots", "overflows", "relax_calls",
                "entry_arcs", "entries_bf", "reached_bf", "entries_br",
                "reached_br"))
    assert occ["entry_arcs"] == sum(r[3] for r in occ["rows_bf"]
                                    + occ["rows_br"])
    assert occ["reached_bf"] == int(tr_bf.reached)
    assert occ["entries_br"] == occ["reached_br"] == int(tr_br.reached)
    # a second batch accumulates the sums and replaces the rows
    ex.step_sum(src, np.ones(NB, bool))
    again = ex.occupancy_summary()
    assert again["arc_slots"] == 2 * occ["arc_slots"]
    for key in ("entry_arcs", "entries_bf", "reached_bf"):
        assert again[key] == 2 * occ[key]
    assert again["rows_bf"] == occ["rows_bf"]
    assert lam.shape == (g.n,)


def _traced_batch(adj, src):
    from repro.core.mfbc import mfbc_batch_moments_traced

    return mfbc_batch_moments_traced(adj, jnp.asarray(src),
                                     jnp.ones(NB, bool))


_NO_SCOPES = r"""
import contextlib, importlib
import jax, jax.numpy as jnp, numpy as np
from repro.graphs.generators import rmat
A, F, R, C = (importlib.import_module(f"repro.core.{m}")
              for m in ("adjacency", "mfbf", "mfbr", "mfbc"))

g, _ = rmat(6, 4, seed=3, weighted=True, max_weight=4).remove_isolated()
src = jnp.arange(4, dtype=jnp.int32) * 7
val = jnp.array([True, True, True, False])


def run():
    out, hlo = [], ""
    for adj in (A.csr_adj_from_graph(g, n_b=4),
                A.csr_adj_from_graph(g, caps=((1, 1),)),
                A.coo_adj_from_graph(g), A.dense_adj_from_graph(g, block=32)):
        out.append(np.asarray(C.mfbc_batch(adj, src, val)[0]))
        out.append(np.asarray(C.mfbc_batch_moments(adj, src, val)[0]))
        out.append(np.asarray(C.mfbc_batch_moments_traced(adj, src, val)[0]))
        hlo += C.mfbc_batch_moments_traced.lower(adj, src, val).as_text(
            debug_info=True)
    return out, hlo


scoped, hlo = run()
for name in ("mfbf", "mfbr", "init", "update", "relax.pick", "relax.rung0",
             "relax.full_edge", "relax.coo", "relax.dense", "batch.reduce"):
    assert name in hlo, name


@contextlib.contextmanager
def no_scope(name):
    yield


jax.named_scope = no_scope
for m in (A, F, R, C):
    importlib.reload(m)
# ``repro.core`` re-exports functions named like the modules, which a
# reload's ``from repro.core import mfbf`` would pick up
C._mfbf, C._mfbr = F, R
plain, hlo = run()
assert "relax.pick" not in hlo and "mfbf/" not in hlo
for a, b in zip(scoped, plain):
    assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
print("BITWISE-OK", len(scoped))
"""


def test_named_scopes_leave_lambda_bitwise_equal():
    """The scoped programs against the same programs built with
    ``jax.named_scope`` as a no-op (in a fresh process, which reloads the
    core modules): λ is bitwise equal on every backend."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", _NO_SCOPES], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "BITWISE-OK 12" in out.stdout
