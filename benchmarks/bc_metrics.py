"""One upload, many analytics: the metric-generic serving benchmark.

Two scenarios over a single R-MAT graph uploaded once:

* **gateway** — one HTTP gateway serves betweenness, closeness, k-hop
  reachability and connected components through the same ``/v1/bc``
  endpoint. Per metric: the cold solve wall time, the identical repeat
  (must be a content-addressed cache hit with a byte-identical payload),
  and the executed ``BCPlan``. The leg also proves metric-keyed cache
  *collision-freedom*: all four cached answers stay distinct — a hit
  under one metric never returns another metric's λ vector.
* **fused** — mixed-metric serving throughput through ``BCService``:
  a concurrent burst cycling betweenness and closeness requests (both
  members of the ``"sweep"`` fuse group, so their epochs pack into one
  ``step_segmented`` device batch), driven ``fuse=False`` vs
  ``fuse=True``. The metric is tick-loop sources/sec, same as
  ``benchmarks/bc_serve.py`` — the fused leg must not regress.

The record lands under the ``"metrics"`` key of ``BENCH_serve.json``
(merged like the ``"gateway"`` record); ``tools/check_bench.py``
gates the cache hits, collision-freedom, per-metric plans and the
no-fused-regression floor in CI.

  PYTHONPATH=src python -m benchmarks.bc_metrics            # scale 10
  PYTHONPATH=src python -m benchmarks.bc_metrics --smoke    # scale 8, CI
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Tuple
import urllib.error
import urllib.request

# (metric, hops, ε) legs through the gateway — one graph upload serves
# them all. Components is exact (ε ignored: cached at ε=0, any request
# hits); khop carries its hop bound into the cache key.
GW_LEGS: Tuple[Tuple[str, int, float], ...] = (
    ("betweenness", 0, 0.15),
    ("closeness", 0, 0.15),
    ("khop", 2, 0.15),
    ("components", 0, 0.05),
)


def _post(base: str, doc: Dict) -> Tuple[int, Dict]:
    req = urllib.request.Request(f"{base}/v1/bc",
                                 data=json.dumps(doc).encode(),
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(base: str, path: str) -> Dict:
    with urllib.request.urlopen(f"{base}{path}") as r:
        return json.loads(r.read())


def _poll_done(base: str, rid: int, timeout_s: float = 120.0) -> Dict:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        doc = _get(base, f"/v1/bc/{rid}")
        if doc["status"] in ("done", "error"):
            assert doc["status"] == "done", doc
            return doc
        time.sleep(0.002)
    raise RuntimeError(f"rid {rid} not done within {timeout_s}s")


def _submit_timed(base: str, doc: Dict) -> Tuple[float, int, Dict]:
    t0 = time.monotonic()
    st, resp = _post(base, doc)
    if resp.get("status") != "done":
        resp = _poll_done(base, resp["rid"])
    return time.monotonic() - t0, st, resp


def _payload(metric: str, hops: int, eps: float) -> Dict:
    doc = {"graph": "web", "eps": eps, "metric": metric}
    if hops:
        doc["hops"] = hops
    return doc


def _leg_key(metric: str, hops: int) -> str:
    return f"{metric}:{hops}" if hops else metric


def bench_gateway_metrics(g) -> Dict:
    """All metrics over the wire from one upload, plus cache isolation."""
    from repro.serve import BCGateway, BCService, GatewayConfig, start_gateway

    def gateway():
        svc = BCService({"web": g}, checkpoints=True)
        return start_gateway(BCGateway(svc, GatewayConfig(horizon_s=1e9)))

    # jit warm-up on a throwaway gateway (module-level jitted steps
    # cache by shape): the timed legs measure serving, not compilation
    warm = gateway()
    try:
        for metric, hops, eps in GW_LEGS:
            _submit_timed(warm.url, _payload(metric, hops, eps))
    finally:
        warm.close()

    srv = gateway()
    per_metric: Dict[str, Dict] = {}
    try:
        base = srv.url
        cold_results: Dict[str, Dict] = {}
        for metric, hops, eps in GW_LEGS:
            key = _leg_key(metric, hops)
            cold_s, _, cold = _submit_timed(base, _payload(metric, hops, eps))
            cached_s, st, cached = _submit_timed(
                base, _payload(metric, hops, eps))
            cold_results[key] = cold["result"]
            per_metric[key] = {
                "eps": eps,
                "cold_s": cold_s,
                "cached_s": cached_s,
                "cache_hit": st == 200 and bool(cached.get("cached")),
                "cache_identical": cached["result"] == cold["result"],
                "plan": cold["result"]["plan"],
            }
        m = _get(base, "/v1/metrics")
    finally:
        srv.close()

    # collision-freedom: every metric's cached answer is its own — no
    # two metrics share a λ vector (they are different analytics)
    lams = [tuple(r["lam"]) for r in cold_results.values()]
    collision_free = (len(set(lams)) == len(lams)
                      and all(p["cache_identical"]
                              for p in per_metric.values()))
    return {
        "n_uploads": 1,
        "legs": [list(leg) for leg in GW_LEGS],
        "per_metric": per_metric,
        "collision_free": collision_free,
        "cache": m.get("cache", {}),
        "admission_correction": m.get("admission_correction", {}),
    }


# ----------------------------------------------- mixed-metric fused leg
# betweenness and closeness share the "sweep" fuse group: their ragged
# epoch demand packs into one segmented device batch. The ε mix keeps
# per-request plans distinct (same multi-tenant shape as bc_serve).
METRIC_MIX: Tuple[Tuple[str, float], ...] = (
    ("betweenness", 0.1), ("closeness", 0.1),
    ("betweenness", 0.3), ("closeness", 0.3),
)


def _mixed_requests(concurrency: int, seed: int) -> List:
    from repro.serve.bc_service import BCRequest

    return [BCRequest(rid=i, graph="web", k=10,
                      metric=METRIC_MIX[i % len(METRIC_MIX)][0],
                      eps=METRIC_MIX[i % len(METRIC_MIX)][1],
                      delta=0.1, rule="normal", seed=seed + i)
            for i in range(concurrency)]


def _drive(svc, reqs, max_ticks: int = 10_000) -> Tuple[Dict, List]:
    for r in reqs:
        svc.submit(r)
    t0 = time.time()
    sources = 0
    ticks = 0
    while (svc.queue or svc.active) and ticks < max_ticks:
        sources += svc.step()
        ticks += 1
    seconds = time.time() - t0
    out = svc.finished
    assert not svc.pending and len(out) == len(reqs), \
        (len(out), len(reqs), svc.pending)
    return {
        "seconds": seconds,
        "sources": sources,
        "sources_per_sec": sources / max(seconds, 1e-9),
        "ticks": ticks,
        "n_requests": len(reqs),
        "all_converged": all(r.converged for r in out),
    }, out


def bench_mixed_fused(g, *, concurrency: int = 8, n_slots: int = 8,
                      seed: int = 0) -> Dict:
    """Mixed-metric fused vs unfused serving throughput."""
    from repro.serve.bc_service import BCService

    legs: Dict[str, Dict] = {}
    for fuse in (False, True):
        def make_service() -> BCService:
            return BCService({"web": g}, n_slots=n_slots, fuse=fuse)

        _drive(make_service(), _mixed_requests(concurrency, seed))  # warm
        rec, out = _drive(make_service(), _mixed_requests(concurrency, seed))
        plans = {id(r.plan): r.plan.to_json() for r in out}
        rec.update(fused=fuse, plans=list(plans.values()))
        legs["fused" if fuse else "unfused"] = rec

    return {
        "concurrency": concurrency,
        "n_slots": n_slots,
        "metric_mix": [list(x) for x in METRIC_MIX],
        "legs": legs,
        "mixed_speedup": (legs["fused"]["sources_per_sec"]
                          / max(legs["unfused"]["sources_per_sec"], 1e-9)),
    }


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json",
                    help="merged into this record's 'metrics' key "
                         "(other keys preserved)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (scale 8)")
    args = ap.parse_args(argv)

    from repro.graphs.generators import from_spec
    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()

    scale = 8 if args.smoke else args.scale
    g = from_spec("rmat", scale=scale, degree=args.degree, seed=args.seed)
    g, _ = g.remove_isolated()

    mrec = {
        "name": f"bc_metrics_rmat_s{scale}_e{args.degree}",
        "n": g.n,
        "m": g.m,
        "gateway": bench_gateway_metrics(g),
        "fused": bench_mixed_fused(g, seed=args.seed),
    }

    rec = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            rec = json.load(f)
    rec["metrics"] = mrec
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)

    gw = mrec["gateway"]
    print(f"[bc_metrics] n={g.n} m={g.m} (one upload, "
          f"{len(gw['per_metric'])} metrics)")
    for key, p in gw["per_metric"].items():
        print(f"[bc_metrics] {key:>12} cold {p['cold_s'] * 1e3:8.1f} ms   "
              f"cached {p['cached_s'] * 1e3:6.1f} ms "
              f"(hit={p['cache_hit']}, identical={p['cache_identical']}, "
              f"backend={p['plan'].get('backend')})")
    print(f"[bc_metrics] cache collision-free across metrics: "
          f"{gw['collision_free']}")
    fz = mrec["fused"]
    for leg, r in fz["legs"].items():
        print(f"[bc_metrics] mixed {leg:>7} {r['sources_per_sec']:8.1f} "
              f"src/s ({r['ticks']} ticks, converged={r['all_converged']})")
    print(f"[bc_metrics] mixed-metric fused speedup: "
          f"{fz['mixed_speedup']:.2f}x")
    print(f"[bc_metrics] wrote {args.out}")
    return mrec


if __name__ == "__main__":
    main()
