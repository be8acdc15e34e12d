"""Run the BC engine end to end on one TPU chip, through its entry points.

  python chip_smoke.py [--seed 0]
  python chip_smoke.py --four-chips [--seed 0]

One process; every graph is generated from ``--seed``, so the run needs
no file outside the repository and no network. Without a TPU it exits
non-zero before any phase runs.

Phases (one chip):

* **exact** — a Graph500-class R-MAT graph (a, b, c = 0.57, 0.19, 0.19,
  edge factor 16) at scale 18; ``repro.bc.solve`` in exact mode on a
  fixed source set with the planner's own backend choice, as
  ``repro.launch.bc_run`` does; λ checked against ``brandes_bc``.
* **served** — the same graph behind ``BCService`` + ``BCGateway`` on an
  ephemeral localhost port, as ``repro.launch.bc_serve`` runs it:
  approximate betweenness (ε = 0.05, top-10), closeness, 2-hop khop,
  components (checked against ``cc_ref``) and a repeat of the first
  request, which must be a cache hit.
* **dense** — a weighted R-MAT graph (integer weights in [1, 100]) at
  scale 13 on the dense backend with the Pallas kernels and
  without; both checked against ``brandes_bc``, and the kernel leg's
  compiled program must hold a ``tpu_custom_call`` (the Mosaic kernel,
  not the interpreter).

``--four-chips`` runs only the mesh path and what it is compared with:
exact betweenness of one source batch on a 2x2 (data, model) mesh
against the same batch on one device, on an R-MAT graph at scale 15
(dense A and Aᵀ fit one chip for the comparison), ``iters`` pinned to
the batch's largest BFS hop eccentricity plus one.

Earlier lines report per-phase seconds, set-up (compile) seconds, the
executed plans and peak device memory; the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RTOL = 1e-4
EPS = 0.05
SCALE = 18  # exact + served phases: fits 16 GB at the planner's n_b
CHECK_SOURCES = 2  # exact-sweep sources Brandes checks (~40 s each on a CPU)
DENSE_SCALE = 13  # weighted graph small enough for dense adjacency
DENSE_SOURCES = 8
MESH_SCALE = 15
MESH_SOURCES = 16
SERVED_TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def peak_bytes() -> Optional[int]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def assert_close(got, want, what: str, rtol: float = RTOL) -> None:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want)
    bound = rtol * np.abs(want) + 1e-6
    bad = int(np.count_nonzero(err > bound))
    check(bad == 0, f"{what}: {bad} of {got.size} entries off by more than "
                    f"rtol={rtol} (max abs err {float(err.max()):.3g})")


def rmat_graph(scale: int, seed: int, *, weighted: bool = False):
    from repro.graphs.generators import rmat

    g, _ = rmat(scale, 16, seed=seed, weighted=weighted).remove_isolated()
    return g


def hop_eccentricity(g, sources) -> int:
    """Largest BFS hop distance from any of ``sources`` (host numpy)."""
    import numpy as np

    from repro.graphs.formats import coo_to_csr

    indptr, indices, _ = coo_to_csr(g)
    worst = 0
    for s in np.asarray(sources):
        seen = np.zeros(g.n, bool)
        seen[s] = True
        frontier = np.array([s], np.int64)
        depth = 0
        while True:
            lo, hi = indptr[frontier], indptr[frontier + 1]
            lens = hi - lo
            idx = (np.repeat(lo - np.cumsum(lens) + lens, lens)
                   + np.arange(int(lens.sum())))
            nxt = np.unique(indices[idx])
            nxt = nxt[~seen[nxt]]
            if nxt.size == 0:
                break
            seen[nxt] = True
            frontier = nxt
            depth += 1
        worst = max(worst, depth)
    return worst


# ------------------------------------------------------------------ phases
def phase_device() -> Dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu",
          f"no TPU: JAX's first device is {dev['platform']!r} ({devs[0]})")
    return dev


def phase_exact(g, sources) -> Dict:
    """Exact BC on ``sources`` through plan -> executor -> solve."""
    from repro.bc import BCQuery, build_executor, solve
    from repro.bc import plan as bc_plan
    from repro.core import brandes_bc

    query = BCQuery(mode="exact")
    pl = bc_plan(g, query, n_devices=1)
    log(f"exact: {pl.summary()} execution={pl.execution.describe()}"
        f" calibrated={bool(pl.regime.get('calibrated'))}")
    for note in pl.notes:
        log(f"exact: note: {note}")
    t0 = time.perf_counter()
    ex = build_executor(g, pl)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    solve(g, query, plan=pl, executor=ex, sources=sources)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = solve(g, query, plan=pl, executor=ex, sources=sources)
    t_warm = time.perf_counter() - t0
    del ex
    gc.collect()
    t0 = time.perf_counter()
    ref = brandes_bc(g, sources=sources)
    t_ref = time.perf_counter() - t0
    assert_close(out.lam, ref, f"exact λ vs brandes_bc on {len(sources)} "
                               f"sources")
    return {"backend": pl.backend, "n_b": pl.n_b,
            "execution": pl.execution.describe(),
            "calibrated": bool(pl.regime.get("calibrated")),
            "n_sources": int(len(sources)),
            "build_s": t_build, "setup_s": t_cold - t_warm,
            "run_s": t_warm, "brandes_s": t_ref,
            "teps": g.m * len(sources) / max(t_warm, 1e-9)}


def _http(method: str, url: str, deadline: float,
          doc: Optional[Dict] = None):
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    # The gateway answers a poll only between solver ticks (one lock), and
    # a tick that compiles a program holds it for a minute or more.
    timeout = max(deadline - time.monotonic(), 1.0)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())
    except TimeoutError:
        raise SmokeFailure(f"{method} {url}: no answer by the deadline")


def _ask(base: str, doc: Dict, deadline: float) -> Dict:
    """POST one request and poll it to a final state."""
    t0 = time.perf_counter()
    post_status, sub = _http("POST", f"{base}/v1/bc", deadline, doc)
    check(post_status in (200, 202), f"served {doc}: HTTP {post_status} {sub}")
    out = sub
    while out["status"] not in ("done", "error"):
        check(time.monotonic() < deadline,
              f"served {doc}: still {out['status']} at the deadline")
        time.sleep(0.05)
        st, out = _http("GET", f"{base}/v1/bc/{sub['rid']}", deadline)
        check(st == 200, f"poll {sub['rid']}: HTTP {st} {out}")
    check(out["status"] == "done",
          f"served {doc}: {out['status']} {out.get('error')}")
    out["client_s"] = time.perf_counter() - t0
    out["post_status"] = post_status
    return out


def phase_served(g, name: str, timeout_s: float) -> Dict:
    """Four metrics and a repeat over HTTP, as ``repro.launch.bc_serve``
    serves them."""
    import numpy as np

    from repro.core import cc_ref
    from repro.serve import BCGateway, BCService, GatewayConfig, start_gateway

    service = BCService({name: g}, checkpoints=True)
    # One request is in flight at a time, so nothing queues: a horizon
    # as long as the run only keeps a slow cold compile from reading as
    # overload.
    gateway = BCGateway(service, GatewayConfig(horizon_s=timeout_s))
    server = start_gateway(gateway)
    deadline = time.monotonic() + timeout_s
    asks = [("betweenness", {"graph": name, "eps": EPS, "k": 10}),
            ("closeness", {"graph": name, "eps": EPS, "k": 10,
                           "metric": "closeness"}),
            ("khop", {"graph": name, "eps": EPS, "k": 10, "metric": "khop",
                      "hops": 2}),
            ("components", {"graph": name, "k": 10,
                            "metric": "components"}),
            ("repeat", {"graph": name, "eps": EPS, "k": 10})]
    res: Dict[str, Dict] = {}
    try:
        for tag, doc in asks:
            out = _ask(server.url, doc, deadline)
            r = out["result"]
            res[tag] = out
            log(f"served {tag}: done in {out['client_s']:.3f}s "
                f"cached={out['cached']} n_samples={r['n_samples']} "
                f"epochs={r['n_epochs']} converged={r['converged']} "
                f"plan={r['plan']['execution']} "
                f"calibrated={bool(r['plan']['regime'].get('calibrated'))}")
        labels = service.executor_for(name).labels()
    finally:
        server.close()
    check(res["repeat"]["cached"] and res["repeat"]["post_status"] == 200,
          "repeat of the first request was not a cache hit")
    check(res["repeat"]["result"] == res["betweenness"]["result"],
          "cached repeat differs from the first answer")
    ref = cc_ref(g)
    check(np.array_equal(labels, ref), "component labels differ from cc_ref")
    comp = res["components"]["result"]
    check(np.array_equal(ref[comp["topk"]], comp["lam"])
          and np.array_equal(np.sort(ref)[::-1][:len(comp["lam"])],
                             comp["lam"]),
          "served components top-k differs from cc_ref")
    return {tag: {"client_s": out["client_s"], "cached": out["cached"],
                  "n_samples": out["result"]["n_samples"],
                  "converged": out["result"]["converged"]}
            for tag, out in res.items()}


def _compiled_text(g, plan, sources) -> str:
    """HLO of the batch step the executor runs for ``plan``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.bc import backend_spec
    from repro.core.mfbc import mfbc_batch

    adj = backend_spec(plan.backend).make_adjacency(g, plan)
    src = np.zeros(plan.n_b, np.int32)
    src[:len(sources)] = sources
    val = np.arange(plan.n_b) < len(sources)
    return mfbc_batch.lower(adj, jnp.asarray(src),
                            jnp.asarray(val)).compile().as_text()


def phase_dense(g, sources) -> Dict:
    """Dense exact BC with and without the Pallas kernels."""
    from repro.bc import BCQuery, ExecutionConfig, build_executor, solve
    from repro.bc import plan as bc_plan
    from repro.core import brandes_bc

    t0 = time.perf_counter()
    ref = brandes_bc(g, sources=sources)
    t_ref = time.perf_counter() - t0
    out: Dict[str, Dict] = {}
    lam = {}
    for use_kernel in (True, False):
        tag = "kernel" if use_kernel else "jnp"
        query = BCQuery(mode="exact", n_b=len(sources),
                        execution=ExecutionConfig(backend="dense",
                                                  use_kernel=use_kernel))
        pl = bc_plan(g, query, n_devices=1)
        ex = build_executor(g, pl)
        t0 = time.perf_counter()
        solve(g, query, plan=pl, executor=ex, sources=sources)
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        lam[tag] = solve(g, query, plan=pl, executor=ex, sources=sources).lam
        t_warm = time.perf_counter() - t0
        del ex
        assert_close(lam[tag], ref, f"dense/{tag} λ vs brandes_bc")
        out[tag] = {"execution": pl.execution.describe(), "n_b": pl.n_b,
                    "setup_s": t_cold - t_warm, "run_s": t_warm}
        log(f"dense {tag}: {pl.execution.describe()} setup "
            f"{t_cold - t_warm:.2f}s run {t_warm:.3f}s")
        if use_kernel:
            check("tpu_custom_call" in _compiled_text(g, pl, sources),
                  "dense kernel leg compiled without a tpu_custom_call")
    assert_close(lam["kernel"], lam["jnp"], "dense kernel λ vs jnp λ")
    out["brandes_s"] = t_ref
    return out


def phase_mesh(g, sources, iters: int) -> Dict:
    """Exact BC of one batch on a 2x2 mesh vs on one device."""
    from repro.bc import BCQuery, ExecutionConfig, solve
    from repro.launch.mesh import mesh_from_spec

    mesh = mesh_from_spec("2x2")
    q_mesh = BCQuery(mode="exact", n_b=len(sources), iters=iters)
    q_one = BCQuery(mode="exact", n_b=len(sources),
                    execution=ExecutionConfig(backend="dense",
                                              placement="single_host"))
    out: Dict[str, Dict] = {}
    lam = {}
    for tag, q, m in (("mesh", q_mesh, mesh), ("single", q_one, None)):
        t0 = time.perf_counter()
        res = solve(g, q, mesh=m, sources=sources)
        t_total = time.perf_counter() - t0
        lam[tag] = res.lam
        out[tag] = {"plan": res.plan.summary(),
                    "execution": res.plan.execution.describe(),
                    "seconds": t_total}
        log(f"mesh phase {tag}: {res.plan.summary()} "
            f"execution={res.plan.execution.describe()} {t_total:.2f}s")
        del res
        gc.collect()
    assert_close(lam["mesh"], lam["single"], "mesh λ vs single-device λ")
    out["iters"] = iters
    return out


# -------------------------------------------------------------------- main
def run(args) -> Dict:
    import numpy as np

    from repro.launch.runtime import enable_compile_cache

    cache = enable_compile_cache()
    dev = phase_device()
    log(f"device {dev}; compile cache {cache}")
    rng = np.random.default_rng(args.seed)
    phases: Dict[str, Dict] = {}

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        phases[name] = fn(*a)
        phases[name]["phase_s"] = time.perf_counter() - t0
        phases[name]["peak_bytes"] = peak_bytes()
        log(f"phase {name}: {json.dumps(phases[name], default=str)}")

    def pick(g, k):
        return np.sort(rng.choice(g.n, size=k, replace=False)).astype(np.int32)

    if args.four_chips:
        check(dev["count"] == 4, f"--four-chips needs 4 devices, "
                                 f"JAX sees {dev['count']}")
        g = rmat_graph(MESH_SCALE, args.seed)
        src = pick(g, MESH_SOURCES)
        iters = hop_eccentricity(g, src) + 1
        log(f"mesh graph rmat_s{MESH_SCALE}: n={g.n} m={g.m} iters={iters}")
        timed("mesh", phase_mesh, g, src, iters)
        return dev

    t0 = time.perf_counter()
    g = rmat_graph(SCALE, args.seed)
    log(f"graph rmat_s{SCALE}: n={g.n} m={g.m} "
        f"({time.perf_counter() - t0:.1f}s to generate)")
    timed("exact", phase_exact, g, pick(g, CHECK_SOURCES))
    timed("served", phase_served, g, f"rmat_s{SCALE}", SERVED_TIMEOUT_S)
    del g
    gc.collect()
    g = rmat_graph(DENSE_SCALE, args.seed, weighted=True)
    log(f"dense graph rmat_s{DENSE_SCALE} weighted: n={g.n} m={g.m}")
    timed("dense", phase_dense, g, pick(g, DENSE_SOURCES))
    return dev


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed every graph and source set is made from")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh path and its one-device "
                         "comparison")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        dev = run(args)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
