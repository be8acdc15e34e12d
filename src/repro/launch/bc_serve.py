"""BC gateway launcher: serve registered graphs over HTTP.

  PYTHONPATH=src python -m repro.launch.bc_serve \
      --graph rmat:10:8 --graph ws:8:4 [--port 8080] \
      [--horizon 5.0] [--overload reject|degrade] [--degrade-eps 0.2] \
      [--slots 4] [--no-cache-refine]

Each ``--graph kind:scale:degree`` spec is generated, registered with a
checkpointing ``BCService``, and served by ``repro.serve.BCGateway`` on
``--port`` (0 picks an ephemeral port, printed on startup). Ctrl-C
shuts down cleanly. Try it::

  curl -s localhost:8080/v1/graphs
  curl -s -XPOST localhost:8080/v1/bc \
      -d '{"graph": "rmat:10:8", "eps": 0.1, "priority": "interactive"}'
  curl -s localhost:8080/v1/bc/0
  curl -s localhost:8080/v1/metrics
"""
from __future__ import annotations

import argparse
import time

from repro.graphs.generators import from_spec
from repro.launch.runtime import enable_compile_cache
from repro.serve import BCGateway, BCService, GatewayConfig, start_gateway


def _parse_graph(spec: str):
    kind, scale, degree = (spec.split(":") + ["8"])[:3]
    return spec, from_spec(kind, scale=int(scale), degree=float(degree))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", action="append", default=None,
                    help="kind:scale[:degree], repeatable "
                         "(default rmat:8:8)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--horizon", type=float, default=5.0,
                    help="admission horizon in predicted seconds")
    ap.add_argument("--overload", choices=("reject", "degrade"),
                    default="reject")
    ap.add_argument("--degrade-eps", type=float, default=0.2)
    ap.add_argument("--cache-entries", type=int, default=256)
    ap.add_argument("--no-cache-refine", action="store_true",
                    help="treat looser-ε cache entries as misses")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--run-for", type=float, default=None,
                    help="serve for N seconds then exit (tests/demos)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    graphs = dict(_parse_graph(s) for s in (args.graph or ["rmat:8:8"]))
    service = BCService(graphs, n_slots=args.slots, checkpoints=True)
    gateway = BCGateway(service, GatewayConfig(
        horizon_s=args.horizon, overload=args.overload,
        degrade_eps=args.degrade_eps, cache_entries=args.cache_entries,
        refine=not args.no_cache_refine))
    server = start_gateway(gateway, host=args.host, port=args.port)
    for name, g in graphs.items():
        print(f"  graph {name}: n={g.n} m={g.m} "
              f"digest={service.digest(name)[:12]}")
    print(f"bc gateway listening on {server.url} "
          f"(horizon={args.horizon}s overload={args.overload})")
    try:
        if args.run_for is not None:
            time.sleep(args.run_for)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.close()
        print("gateway closed")


if __name__ == "__main__":
    main()
