"""Fused vs unfused serving throughput (the cross-request batching win).

Drives ``serve.BCService`` at 1–16 concurrent approximate-BC queries on
one R-MAT graph, twice per concurrency level: ``fuse=False`` (the
pre-fusion behavior — every request's epoch runs as its own batch,
padded to the graph-wide ``n_b``) and ``fuse=True`` (per-request (ε, δ)
plans via ``repro.bc.plan_for_request`` + slot-tagged fused batches
through the executors' ``step_segmented``). The metric is tick-loop
throughput in *source samples per second*: fusion packs several
requests' ragged epoch demand into shared power-of-two buckets, so the
fixed per-batch cost (kernel dispatch; on a mesh, the fused moments
all-reduce) and the padding waste are amortized across queries.

The request mix cycles (ε, seed) so per-request plans differ — exactly
the ragged multi-tenant demand fusion exists for. Each leg is jit-warmed
by a throwaway identical run (module-level jitted steps cache by shape),
so timings are steady-state serving, not XLA compilation.

A second scenario exercises the QoS scheduler under *mixed-tier* load:
a burst of loose-ε batch-tier requests submitted ahead of tight-ε
interactive ones, driven twice — ``pack="fifo"`` (the legacy
strict-arrival baseline: interactive work queues behind the batch
burst) and ``pack="deadline"`` (EDF admission + deadline-slack
draining + a ``tick_budget`` that preempts batch slots mid-epoch).
The metric is per-tier p50/p95 *latency* (submit → retirement): the
tight-ε tier's p95 must beat the FIFO baseline leg without giving up
the fused throughput.

Everything lands in ``BENCH_serve.json`` with the per-request executed
``BCPlan``s (tiers included) and the graph capacity plan recorded next
to the timings; ``tools/check_bench.py`` asserts the record's shape —
including the tight-tier p95 win — in CI.

  PYTHONPATH=src python -m benchmarks.bc_serve            # scale 10
  PYTHONPATH=src python -m benchmarks.bc_serve --smoke    # scale 8, CI
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Sequence

# (ε, δ) mix cycled over concurrent requests: distinct accuracy contracts
# produce distinct per-request plans (tight ε → large n_b/budget, loose
# ε → small n_b and a sub-batch Hoeffding cap) and ragged epoch demand —
# the multi-tenant shape fusion is for. The loose tiers model cheap
# "find the hubs" queries; without fusion every one of their under-
# filled epochs pads to the graph-wide n_b.
EPS_MIX = (0.05, 0.3, 0.1, 0.4)


def _requests(concurrency: int, rule: str, seed: int):
    from repro.serve.bc_service import BCRequest

    return [BCRequest(rid=i, graph="web", k=10, eps=EPS_MIX[i % len(EPS_MIX)],
                      delta=0.1, rule=rule, seed=seed + i)
            for i in range(concurrency)]


def _drive(svc, reqs, max_ticks: int = 10_000):
    """Submit, tick to completion, count sources; returns (rec, responses)."""
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


def bench_bc_serve(scale: int = 10, degree: int = 8,
                   levels: Sequence[int] = (1, 2, 4, 8, 16),
                   n_slots: int = 16, rule: str = "normal",
                   seed: int = 0) -> Dict:
    """Fused-vs-unfused serving sweep; returns the BENCH record."""
    from repro.graphs.generators import from_spec
    from repro.serve.bc_service import BCService

    g = from_spec("rmat", scale=scale, degree=degree, seed=seed)
    g, _ = g.remove_isolated()

    def make_service(fuse: bool) -> BCService:
        return BCService({"web": g}, n_slots=n_slots, fuse=fuse)

    runs: List[Dict] = []
    graph_plan = None
    for concurrency in levels:
        for fuse in (False, True):
            reqs = _requests(concurrency, rule, seed)
            # throwaway identical run: compiles every (bucket, variant)
            # shape this leg will touch, so the timed run is steady-state
            _drive(make_service(fuse), list(reqs))
            svc = make_service(fuse)
            rec, out = _drive(svc, list(reqs))
            rec.update(concurrency=concurrency, fused=fuse)
            # The per-request plans that *sized* each run (deduped:
            # requests sharing (ε, δ, rule) share a cached plan object;
            # the unfused leg is sized by the graph capacity plan). The
            # executor configuration that ran them is graph_plan.
            plans = {id(r.plan): r.plan.to_json() for r in out}
            rec["plans"] = list(plans.values())
            runs.append(rec)
            graph_plan = svc.plan_for("web").to_json()

    speedups = {}
    by = {(r["concurrency"], r["fused"]): r for r in runs}
    for c in levels:
        speedups[str(c)] = (by[(c, True)]["sources_per_sec"]
                            / max(by[(c, False)]["sources_per_sec"], 1e-9))
    return {
        "name": f"bc_serve_rmat_s{scale}_e{degree}",
        "n": g.n,
        "m": g.m,
        "rule": rule,
        "n_slots": n_slots,
        "eps_mix": list(EPS_MIX),
        "levels": list(levels),
        "graph_plan": graph_plan,
        "runs": runs,
        "fused_speedup": speedups,
    }


# -------------------------------------------------- mixed-tier QoS leg
# (ε, tier) per QoS class: the interactive tier is the *tight*-ε work —
# many sampling epochs, the requests whose tail latency the deadline
# scheduler exists to protect; the batch tier is loose-ε background
# load submitted ahead of it (the FIFO baseline's worst case).
TIER_MIX = {"interactive": 0.05, "batch": 0.15}


def _percentile(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted sequence."""
    if not sorted_vals:
        return 0.0
    rank = max(1, int(-(-q / 100.0 * len(sorted_vals) // 1)))
    return float(sorted_vals[min(rank, len(sorted_vals)) - 1])


def _mixed_requests(n_interactive: int, n_batch: int, rule: str, seed: int):
    """Batch burst first, interactive arrivals behind it — FIFO admits
    the burst, EDF jumps the interactive tier over it."""
    from repro.serve.bc_service import BCRequest

    reqs = []
    for i in range(n_batch):
        reqs.append(BCRequest(rid=i, graph="web", k=10,
                              eps=TIER_MIX["batch"], delta=0.1, rule=rule,
                              seed=seed, priority="batch",
                              tenant=f"bg{i % 2}"))
    for i in range(n_interactive):
        reqs.append(BCRequest(rid=n_batch + i, graph="web", k=10,
                              eps=TIER_MIX["interactive"], delta=0.1,
                              rule=rule, seed=seed, priority="interactive",
                              tenant="fg"))
    return reqs


def bench_mixed_tiers(scale: int = 10, degree: int = 8, *,
                      n_interactive: int = 4, n_batch: int = 8,
                      n_slots: int = 4, rule: str = "normal", seed: int = 0,
                      tick_budget: int = 256) -> Dict:
    """Per-tier latency under mixed load: FIFO baseline vs QoS legs."""
    from repro.graphs.generators import from_spec
    from repro.serve.bc_service import BCService

    g = from_spec("rmat", scale=scale, degree=degree, seed=seed)
    g, _ = g.remove_isolated()

    legs: Dict[str, Dict] = {}
    for leg, pack, budget in (("fifo", "fifo", None),
                              ("deadline", "deadline", tick_budget)):
        def make_service() -> BCService:
            return BCService({"web": g}, n_slots=n_slots, pack=pack,
                             tick_budget=budget)

        # throwaway identical run: jit-warm every shape this leg touches
        _drive(make_service(), _mixed_requests(n_interactive, n_batch,
                                               rule, seed))
        rec, out = _drive(make_service(),
                          _mixed_requests(n_interactive, n_batch, rule,
                                          seed))
        per_tier = {}
        for tier in TIER_MIX:
            lats = sorted(r.latency_s for r in out if r.tier == tier)
            per_tier[tier] = {"n": len(lats),
                              "p50_s": _percentile(lats, 50),
                              "p95_s": _percentile(lats, 95),
                              "max_s": lats[-1] if lats else 0.0}
        plans = {id(r.plan): r.plan.to_json() for r in out}
        rec.update(pack=pack, tick_budget=budget, per_tier=per_tier,
                   plans=list(plans.values()))
        legs[leg] = rec

    p95_fifo = legs["fifo"]["per_tier"]["interactive"]["p95_s"]
    p95_dl = legs["deadline"]["per_tier"]["interactive"]["p95_s"]
    return {
        "n_slots": n_slots,
        "n_interactive": n_interactive,
        "n_batch": n_batch,
        "rule": rule,
        "eps": dict(TIER_MIX),
        "tight_tier": "interactive",
        "legs": legs,
        "tight_p95_speedup": p95_fifo / max(p95_dl, 1e-9),
    }


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--levels", default="1,2,4,8,16",
                    help="comma-separated concurrency levels")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--rule", default="normal",
                    choices=["normal", "bernstein"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (scale 8, levels 1,2,4)")
    ap.add_argument("--no-mixed", action="store_true",
                    help="skip the mixed-tier QoS scenario")
    args = ap.parse_args(argv)

    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    scale = 8 if args.smoke else args.scale
    levels = ((1, 2, 4) if args.smoke
              else tuple(int(x) for x in args.levels.split(",")))
    rec = bench_bc_serve(scale=scale, degree=args.degree, levels=levels,
                         n_slots=args.slots, rule=args.rule, seed=args.seed)
    if not args.no_mixed:
        rec["mixed_tier"] = bench_mixed_tiers(
            scale=scale, degree=args.degree, rule=args.rule, seed=args.seed)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[bc_serve] n={rec['n']} m={rec['m']} slots={rec['n_slots']} "
          f"eps_mix={rec['eps_mix']}")
    for r in rec["runs"]:
        tag = "fused  " if r["fused"] else "unfused"
        print(f"[bc_serve] c={r['concurrency']:>2} {tag} "
              f"{r['sources_per_sec']:8.1f} src/s "
              f"({r['sources']} sources, {r['ticks']} ticks, "
              f"{r['seconds']:.2f}s, converged={r['all_converged']})")
    for c, s in rec["fused_speedup"].items():
        print(f"[bc_serve] fused speedup @ {c} concurrent: {s:.2f}x")
    mt = rec.get("mixed_tier")
    if mt:
        for leg, r in mt["legs"].items():
            for tier, p in r["per_tier"].items():
                print(f"[bc_serve] mixed {leg:>8} {tier:>11} "
                      f"p50={p['p50_s']:.3f}s p95={p['p95_s']:.3f}s "
                      f"(n={p['n']})")
            print(f"[bc_serve] mixed {leg:>8} "
                  f"{r['sources_per_sec']:8.1f} src/s over {r['ticks']} ticks")
        print(f"[bc_serve] mixed tight-tier p95 speedup "
              f"(fifo/deadline): {mt['tight_p95_speedup']:.2f}x")
    print(f"[bc_serve] wrote {args.out}")
    return rec


if __name__ == "__main__":
    main()
