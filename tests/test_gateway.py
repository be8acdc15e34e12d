"""HTTP gateway contract tests — a real server on an ephemeral port.

Every test drives the wire protocol end to end (urllib against
``start_gateway``'s ThreadingHTTPServer), not the gateway object:
submit → poll → done, cache hits returning byte-identical payloads,
looser-ε entries answering instantly with ``refining=true`` and then
refining to a result bitwise-equal to a from-scratch tight run, and
synthetic overload bursts producing 429/degrade without starving the
interactive tier.
"""
import json
import time
import urllib.error
import urllib.request

import pytest

from repro.graphs.generators import rmat
from repro.serve import BCGateway, BCService, GatewayConfig, start_gateway
from repro.serve.bc_service import BCRequest

_CACHE = {}


def _graph():
    if "g" not in _CACHE:
        g = rmat(6, 8, seed=5)
        g, _ = g.remove_isolated()
        _CACHE["g"] = g
    return _CACHE["g"]


def _server(**cfg):
    svc = BCService({"web": _graph()}, checkpoints=True)
    gw = BCGateway(svc, GatewayConfig(**cfg))
    return start_gateway(gw)


def _post(base, doc):
    req = urllib.request.Request(f"{base}/v1/bc",
                                 data=json.dumps(doc).encode(),
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(base, path):
    try:
        with urllib.request.urlopen(f"{base}{path}") as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _poll_done(base, rid, timeout_s=30.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        st, doc = _get(base, f"/v1/bc/{rid}")
        assert st == 200
        if doc["status"] in ("done", "error"):
            return doc
        time.sleep(0.005)
    raise AssertionError(f"rid {rid} not done within {timeout_s}s")


# --------------------------------------------------------------- lifecycle
def test_submit_poll_done_and_cached_repeat():
    """The basic contract: cold submit is accepted and completes with a
    full result payload; an identical repeat answers instantly from the
    cache with the byte-identical payload."""
    srv = _server(horizon_s=30.0)
    try:
        base = srv.url
        st, doc, _ = _post(base, {"graph": "web", "eps": 0.15, "k": 10})
        assert st == 202 and doc["status"] == "queued"
        assert set(doc["queue_depth"]) == {"interactive", "normal", "batch"}
        rid = doc["rid"]

        done = _poll_done(base, rid)
        assert done["status"] == "done" and not done["cached"]
        res = done["result"]
        assert res["graph"] == "web" and len(res["topk"]) == 10
        assert res["converged"] and res["digest"]
        assert res["plan"]["n_b"] > 0
        assert done["latency_s"] > 0

        # identical repeat: HTTP 200 straight from the cache, payload
        # verbatim (the result's rid names the run that produced it)
        st2, doc2, _ = _post(base, {"graph": "web", "eps": 0.15, "k": 10})
        assert st2 == 200 and doc2["status"] == "done" and doc2["cached"]
        assert doc2["result"] == res
        assert doc2["rid"] != rid

        # a *looser* request is also a hit on the tighter entry
        st3, doc3, _ = _post(base, {"graph": "web", "eps": 0.3, "k": 10})
        assert st3 == 200 and doc3["cached"]
        assert doc3["result"] == res
    finally:
        srv.close()


def test_refine_serves_stale_then_bitwise_tight():
    """A tighter-ε request against a looser cached entry answers
    immediately (status=partial, refining=true, the looser payload),
    then refines from the checkpoint to a result bitwise-equal to a
    from-scratch tight run on a fresh gateway over the same
    (seed, rid) stream."""
    srv = _server(horizon_s=30.0)
    try:
        base = srv.url
        st, doc, _ = _post(base, {"graph": "web", "eps": 0.15, "k": 10})
        loose = _poll_done(base, doc["rid"])["result"]

        st, doc, _ = _post(base, {"graph": "web", "eps": 0.05, "k": 10})
        assert st == 202 and doc["status"] == "partial" and doc["refining"]
        assert doc["result"] == loose  # the stale answer, instantly
        refined = _poll_done(base, doc["rid"])
        assert refined["refined"] and not refined.get("refining")
        ref = refined["result"]
        assert ref["n_samples"] >= loose["n_samples"]
    finally:
        srv.close()

    # scratch leg: a fresh gateway gives the tight request the same rid
    # (0) the loose run had, hence the identical (seed, rid) stream the
    # refinement continued — JSON floats are shortest-repr exact, so
    # equality here is bitwise equality of the float64 results.
    srv2 = _server(horizon_s=30.0)
    try:
        st, doc, _ = _post(srv2.url, {"graph": "web", "eps": 0.05, "k": 10})
        scratch = _poll_done(srv2.url, doc["rid"])["result"]
        for field in ("topk", "lam", "halfwidth", "n_samples", "n_epochs",
                      "converged", "digest"):
            assert ref[field] == scratch[field], field
    finally:
        srv2.close()


# ---------------------------------------------------------------- overload
def test_overload_burst_rejects_without_starving_tight_tier():
    """A loose-tier flood past the horizon draws 429 + Retry-After, but
    an interactive request still admits: admission prices only backlog
    at equal-or-tighter deadlines, which the batch flood is not."""
    svc = BCService({"web": _graph()}, checkpoints=True)
    pred = float(svc.request_plan(
        BCRequest(rid=0, graph="web", eps=0.2)).predicted_seconds)
    gw = BCGateway(svc, GatewayConfig(horizon_s=pred * 1.5,
                                      idle_sleep_s=0.05))
    srv = start_gateway(gw)
    # Hold the solver: a batch run that finished mid-flood would rescale
    # every later price by its observed/predicted latency (the admission
    # correction), which on a slow host alone exceeds the horizon.
    gw.close()
    try:
        base = srv.url
        codes = []
        for _ in range(12):
            st, doc, headers = _post(base, {"graph": "web", "eps": 0.2,
                                            "priority": "batch"})
            codes.append(st)
            if st == 429:
                assert "Retry-After" in headers
                assert doc["retry_after_s"] > 0
                assert doc["backlog_s"] >= 0 and doc["horizon_s"] > 0
        assert 429 in codes, codes  # the flood tripped the gate
        assert 202 in codes, codes  # but not before admitting work

        # tight tier sails through the same overload
        st, doc, _ = _post(base, {"graph": "web", "eps": 0.2,
                                  "priority": "interactive"})
        assert st in (200, 202)
        m = _get(base, "/v1/metrics")[1]
        assert m["tiers"]["batch"]["rejected"] > 0
        assert m["tiers"]["interactive"]["rejected"] == 0
        assert m["tiers"]["interactive"]["admitted"] \
            + m["tiers"]["interactive"]["cache_hits"] >= 1
    finally:
        srv.close()


def test_overload_degrade_records_looser_eps():
    """overload='degrade': past the horizon the request is admitted at
    degrade_eps instead of rejected, with the original ε recorded."""
    svc = BCService({"web": _graph()}, checkpoints=True)
    pred = float(svc.request_plan(
        BCRequest(rid=0, graph="web", eps=0.05)).predicted_seconds)
    gw = BCGateway(svc, GatewayConfig(horizon_s=pred * 0.5,
                                      overload="degrade", degrade_eps=0.3,
                                      idle_sleep_s=0.05))
    srv = start_gateway(gw)
    try:
        base = srv.url
        st, doc, _ = _post(base, {"graph": "web", "eps": 0.05})
        assert st == 202 and doc["degraded_from"] == 0.05
        assert doc["eps"] == 0.3
        done = _poll_done(base, doc["rid"])
        assert done["degraded_from"] == 0.05
        m = _get(base, "/v1/metrics")[1]
        assert m["totals"]["degraded"] == 1 and m["totals"]["rejected"] == 0
    finally:
        srv.close()


# --------------------------------------------------------------- listings
def test_graphs_and_metrics_endpoints():
    srv = _server(horizon_s=30.0)
    try:
        base = srv.url
        st, doc = _get(base, "/v1/graphs")
        assert st == 200 and [g["name"] for g in doc["graphs"]] == ["web"]
        g = doc["graphs"][0]
        assert g["n"] > 0 and g["m"] > 0
        assert isinstance(g["digest"], str) and len(g["digest"]) == 64
        assert g["plan"]["n_b"] > 0

        st, m = _get(base, "/v1/metrics")
        assert st == 200
        assert set(m) == {"tiers", "totals", "cache", "queue_depth",
                          "admission_correction"}
        assert m["cache"]["entries"] == 0
        assert m["admission_correction"] == {}  # nothing observed yet
        assert set(m["queue_depth"]) == {"interactive", "normal", "batch"}
    finally:
        srv.close()


# ----------------------------------------------------- metric-generic wire
def test_metrics_through_the_wire_and_cache_isolation():
    """One upload serves betweenness, closeness, khop and components
    through the same POST endpoint; identical parameters under
    different metrics never share a cache entry."""
    srv = _server(horizon_s=100.0)
    try:
        base = srv.url
        docs = {}
        for payload in ({"graph": "web", "eps": 0.1, "seed": 3},
                        {"graph": "web", "eps": 0.1, "seed": 3,
                         "metric": "closeness"},
                        {"graph": "web", "eps": 0.1, "seed": 3,
                         "metric": "khop", "hops": 2},
                        {"graph": "web", "metric": "components"}):
            st, doc, _ = _post(base, payload)
            assert st == 202, doc
            key = (payload.get("metric", "betweenness"),
                   payload.get("hops", 0))
            docs[key] = _poll_done(base, doc["rid"])
        results = {k: d["result"] for k, d in docs.items()}
        lams = [tuple(r["lam"]) for r in results.values()]
        assert len(set(lams)) == len(lams)  # four distinct analytics

        # repeats hit their OWN per-metric entries, byte-identical
        for payload, key in ((
                {"graph": "web", "eps": 0.1, "seed": 3},
                ("betweenness", 0)), (
                {"graph": "web", "eps": 0.1, "seed": 3,
                 "metric": "closeness"}, ("closeness", 0))):
            st, doc, _ = _post(base, payload)
            assert st == 200 and doc["cached"]
            assert doc["result"] == results[key]

        # components cached as exact (ε = 0): any tighter ε still HITs
        st, doc, _ = _post(base, {"graph": "web", "metric": "components",
                                  "eps": 0.001})
        assert st == 200 and doc["cached"]
        assert doc["result"] == results[("components", 0)]

        # distinct hop bounds are distinct keys: hops=3 misses
        st, doc, _ = _post(base, {"graph": "web", "eps": 0.1, "seed": 3,
                                  "metric": "khop", "hops": 3})
        assert st == 202, doc
        assert _poll_done(base, doc["rid"])["result"] != \
            results[("khop", 2)]

        # bad metric / hops draw 400 at the door
        assert _post(base, {"graph": "web", "metric": "nope"})[0] == 400
        assert _post(base, {"graph": "web", "metric": "khop"})[0] == 400
        assert _post(base, {"graph": "web", "hops": 2})[0] == 400
    finally:
        srv.close()


def test_slow_solver_tightens_admission():
    """The EWMA admission correction: after the gateway observes runs
    slower than predicted, the same submission that admitted before is
    priced past the horizon and refused."""
    svc = BCService({"web": _graph()}, checkpoints=True)
    pred = float(svc.request_plan(
        BCRequest(rid=0, graph="web", eps=0.2)).predicted_seconds)
    backend = svc.request_plan(
        BCRequest(rid=0, graph="web", eps=0.2)).backend
    gw = BCGateway(svc, GatewayConfig(horizon_s=pred * 10))
    doc = gw.submit({"graph": "web", "eps": 0.2})
    assert doc["http_status"] == 202  # uncorrected price fits the horizon

    # solver measured 100x slower than the model's prediction
    gw._observe_latency("betweenness", backend, seconds=pred * 100,
                        predicted=pred)
    doc = gw.submit({"graph": "web", "eps": 0.21})
    assert doc["http_status"] == 429, doc  # corrected price trips the gate
    m = gw.metrics_doc()
    assert m["admission_correction"][f"betweenness/{backend}"] \
        == pytest.approx(100.0)
    # the correction is per-metric: closeness is still priced raw
    doc = gw.submit({"graph": "web", "eps": 0.2, "metric": "closeness"})
    assert doc["http_status"] == 202, doc


def test_poll_streams_progress_history():
    """While a job runs, GET /v1/bc/{rid} carries the estimator's
    epoch-by-epoch (τ, halfwidth) history — the streaming partial
    result — with a stable JSON shape."""
    svc = BCService({"web": _graph()}, n_slots=1)
    gw = BCGateway(svc, GatewayConfig(horizon_s=1000.0))
    doc = gw.submit({"graph": "web", "eps": 0.004, "delta": 0.1})
    assert doc["http_status"] == 202
    rid = doc["rid"]
    seen = None
    for _ in range(200):
        if not gw._work_once():  # one tick + finished-drain, inline
            break
        st = gw.get(rid)
        if st["status"] == "running" and "progress" in st:
            seen = st["progress"]
            json.dumps(st)  # the whole doc must be wire-serializable
            assert set(seen) == {"epochs"}
            taus = [e["tau"] for e in seen["epochs"]]
            assert taus == sorted(taus) and all(
                isinstance(t, int) for t in taus)
            for e in seen["epochs"]:
                assert set(e) == {"tau", "halfwidth"}
                assert e["halfwidth"] is None or (
                    isinstance(e["halfwidth"], float)
                    and e["halfwidth"] >= 0.0)
    assert seen is not None, "no running poll carried progress"
    gw.drain()
    assert gw.get(rid)["status"] == "done"
    assert "progress" not in gw.get(rid)  # final answer supersedes it


def test_error_paths():
    srv = _server(horizon_s=30.0)
    try:
        base = srv.url
        assert _post(base, {"graph": "nope"})[0] == 404
        assert _post(base, {})[0] == 400
        assert _post(base, {"graph": "web", "priority": "urgent"})[0] == 400
        assert _post(base, {"graph": "web", "eps": -1})[0] == 400
        assert _get(base, "/v1/bc/999")[0] == 404
        assert _get(base, "/v1/bc/notanint")[0] == 400
        assert _get(base, "/v1/nope")[0] == 404
        # malformed body
        req = urllib.request.Request(f"{base}/v1/bc", data=b"{not json")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
    finally:
        srv.close()
