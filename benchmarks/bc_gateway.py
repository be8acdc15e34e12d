"""Gateway serving latency: cold vs cached vs refine, plus overload.

Drives the real HTTP gateway (``repro.serve.start_gateway`` on an
ephemeral port, urllib as the client) through the three ways a query
can be answered and records what each costs:

* **cold** — empty cache, full solve: submit → poll → done wall time;
* **cached** — the identical repeat: answered inline from the
  content-addressed cache (one HTTP round trip, no solver);
* **refine** — a tighter-ε query against a looser cached entry: the
  stale answer's time-to-first-result (also one round trip) and the
  time until the checkpointed refinement lands, with the refined
  result checked bitwise against a from-scratch tight run on a fresh
  gateway (the ``repro.bc.refine`` resume contract, over the wire).

A second scenario floods the admission gate: a burst of loose batch-tier
queries sized past the predicted-seconds horizon, with interactive
queries interleaved — once under ``overload="reject"`` (expect 429s on
the flood, none on the tight tier) and once under ``"degrade"`` (expect
looser-ε admissions recorded instead). Per-tier admit/reject/degrade
counters come straight from the gateway's /v1/metrics endpoint.

The record lands under the ``"gateway"`` key of ``BENCH_serve.json``
(merged into the ``bc_serve`` record, like ``mixed_tier``);
``tools/check_bench.py`` gates the cache-hit speedup, the bitwise
refine flag, and no-starvation of the tight tier in CI.

  PYTHONPATH=src python -m benchmarks.bc_gateway            # scale 10
  PYTHONPATH=src python -m benchmarks.bc_gateway --smoke    # scale 8, CI
"""
from __future__ import annotations

import argparse
import json
import os
import time
import urllib.error
import urllib.request
from typing import Dict, Optional, Tuple

EPS_LOOSE = 0.15
EPS_TIGHT = 0.05


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


def _gateway(g, **cfg):
    from repro.serve import (BCGateway, BCService, GatewayConfig,
                             start_gateway)

    svc = BCService({"web": g}, checkpoints=True)
    return start_gateway(BCGateway(svc, GatewayConfig(**cfg)))


def _submit_timed(base: str, doc: Dict) -> Tuple[float, int, Dict]:
    """(seconds to an answer in hand, status, response doc). A cache hit
    answers inside the POST; anything else is submit + poll."""
    t0 = time.monotonic()
    st, resp = _post(base, doc)
    if resp.get("status") != "done":
        resp = _poll_done(base, resp["rid"])
    return time.monotonic() - t0, st, resp


def bench_latency(g) -> Dict:
    """Cold / cached / refine latency over the wire, one graph."""
    # jit warm-up on a throwaway gateway: the timed legs measure
    # serving, not XLA compilation (module-level jitted steps cache
    # by shape across services)
    warm = _gateway(g, horizon_s=1e9)
    try:
        _submit_timed(warm.url, {"graph": "web", "eps": EPS_LOOSE})
        _submit_timed(warm.url, {"graph": "web", "eps": EPS_TIGHT})
    finally:
        warm.close()

    srv = _gateway(g, horizon_s=1e9)
    try:
        base = srv.url
        cold_s, _, cold = _submit_timed(
            base, {"graph": "web", "eps": EPS_LOOSE})
        cached_s, st, cached = _submit_timed(
            base, {"graph": "web", "eps": EPS_LOOSE})
        assert st == 200 and cached["cached"], "expected a cache hit"
        cache_identical = cached["result"] == cold["result"]

        # tighter ε against the loose entry: stale answer now, refined
        # answer when the resumed estimator lands
        t0 = time.monotonic()
        st, doc = _post(base, {"graph": "web", "eps": EPS_TIGHT})
        stale_s = time.monotonic() - t0
        refining = bool(doc.get("refining"))
        refined = _poll_done(base, doc["rid"])
        refine_done_s = time.monotonic() - t0
    finally:
        srv.close()

    # scratch leg: fresh gateway, tight ε directly — rid 0 gives the
    # same (seed, rid) stream the loose run had, so the refined result
    # must match bitwise (JSON floats are shortest-repr exact)
    srv2 = _gateway(g, horizon_s=1e9)
    try:
        _, _, scratch = _submit_timed(
            srv2.url, {"graph": "web", "eps": EPS_TIGHT})
    finally:
        srv2.close()
    refine_bitwise = all(
        refined["result"][f] == scratch["result"][f]
        for f in ("topk", "lam", "halfwidth", "n_samples", "n_epochs"))

    return {
        "cold_s": cold_s,
        "cached_s": cached_s,
        "cached_speedup": cold_s / max(cached_s, 1e-9),
        "cache_identical_payload": cache_identical,
        "refine_stale_s": stale_s,
        "refine_done_s": refine_done_s,
        "refining_flagged": refining,
        "refine_bitwise": refine_bitwise,
        "eps": {"loose": EPS_LOOSE, "tight": EPS_TIGHT},
    }


def bench_overload(g, *, n_burst: int = 12, n_tight: int = 3) -> Dict:
    """Admission under a synthetic burst, reject and degrade policies."""
    from repro.serve import BCService
    from repro.serve.bc_service import BCRequest

    pred = float(BCService({"web": g}).request_plan(
        BCRequest(rid=0, graph="web", eps=EPS_LOOSE)).predicted_seconds)

    legs = {}
    for policy in ("reject", "degrade"):
        # horizon under one predicted request keeps the gate hot for the
        # whole burst regardless of how fast the worker drains; a large
        # idle sleep keeps the burst ahead of the solver
        srv = _gateway(g, horizon_s=max(pred * 1.5, 1e-6),
                       overload=policy, degrade_eps=0.4,
                       idle_sleep_s=0.05)
        try:
            base = srv.url
            codes = {"batch": [], "interactive": []}
            for i in range(n_burst):
                st, _ = _post(base, {"graph": "web", "eps": EPS_LOOSE,
                                     "priority": "batch", "seed": i})
                codes["batch"].append(st)
                if i % (n_burst // max(n_tight, 1)) == 0:
                    st, _ = _post(base, {"graph": "web", "eps": EPS_LOOSE,
                                         "priority": "interactive",
                                         "seed": 1000 + i})
                    codes["interactive"].append(st)
            m = _get(base, "/v1/metrics")
        finally:
            srv.close()
        tiers = m["tiers"]

        def rate(t):
            sub = tiers[t]["submitted"]
            served = (tiers[t]["admitted"] + tiers[t]["cache_hits"]
                      + tiers[t]["cache_refines"])
            return served / sub if sub else 1.0

        legs[policy] = {
            "horizon_s": max(pred * 1.5, 1e-6),
            "predicted_s": pred,
            "n_burst": n_burst,
            "codes": codes,
            "tiers": tiers,
            "rejected": m["totals"]["rejected"],
            "degraded": m["totals"]["degraded"],
            "tight_admit_rate": rate("interactive"),
            "loose_admit_rate": rate("batch"),
        }
    return legs


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json",
                    help="merged into this record's 'gateway' key "
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

    gw_rec = {
        "name": f"bc_gateway_rmat_s{scale}_e{args.degree}",
        "n": g.n,
        "m": g.m,
        "latency": bench_latency(g),
        "overload": bench_overload(g),
    }

    rec = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            rec = json.load(f)
    rec["gateway"] = gw_rec
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)

    lat = gw_rec["latency"]
    print(f"[bc_gateway] n={g.n} m={g.m}")
    print(f"[bc_gateway] cold {lat['cold_s'] * 1e3:8.1f} ms   "
          f"cached {lat['cached_s'] * 1e3:6.1f} ms "
          f"({lat['cached_speedup']:.0f}x, "
          f"identical={lat['cache_identical_payload']})")
    print(f"[bc_gateway] refine: stale answer {lat['refine_stale_s'] * 1e3:.1f} ms, "
          f"refined {lat['refine_done_s'] * 1e3:.1f} ms, "
          f"bitwise={lat['refine_bitwise']}")
    for policy, leg in gw_rec["overload"].items():
        print(f"[bc_gateway] overload[{policy}]: rejected={leg['rejected']} "
              f"degraded={leg['degraded']} tight_admit="
              f"{leg['tight_admit_rate']:.2f} loose_admit="
              f"{leg['loose_admit_rate']:.2f}")
    return gw_rec


if __name__ == "__main__":
    main()
