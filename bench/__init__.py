"""On-chip benchmark of the BC engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything a cell needs is found by
name: ``configs/<config>.json`` (the deployment), ``workloads/<cell>.json``
(the traffic and its fixed parameters), ``traffic/<kind>.py`` (the load
driver) and ``metrics/<name>.py`` (one reader per per-layer metric).
The graph generator, the host reference, the trace reduction and the
peaks table sit beside them, so the yardstick never depends on the code
it measures.
"""
