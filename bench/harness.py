"""Find a cell's files by name, run it, and print its result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its files:

* ``workloads/<cell>.json``: ``config``, ``traffic`` (``kind``, ``mix``
  and the driver's parameters) and ``checks`` (the limit of each number
  that decides ``correct``);
* ``configs/<config>.json``: the deployment;
* ``traffic/<kind>.py``: the load driver, a ``Session(cell, seed)`` with
  ``window(seconds, spans)``, ``end_to_end()``, ``counters()``,
  ``release()``, ``checks()`` and the ``attempted``/``failed`` counts;
* ``metrics/<metric>.py``: one reader per per-layer metric,
  ``read(readings) -> float | None``.

Adding a cell, a configuration, a traffic kind or a metric is adding
files and a ``BENCHMARK.json`` entry; nothing here names any of them.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

from bench import device

BENCH_DIR = Path(__file__).resolve().parent
SPEC_FILE = BENCH_DIR.parent / "BENCHMARK.json"


@dataclasses.dataclass(frozen=True)
class Check:
    """One number that decides ``correct``: it passes at ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    workload: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: Path

    @property
    def traffic(self) -> Dict:
        return self.workload["traffic"]

    def limit(self, check: str) -> float:
        return float(self.workload["checks"][check])


@dataclasses.dataclass
class Readings:
    """What a per-layer reader may read after a traced window."""

    window_s: float
    counters: Dict
    reduction: Optional[object]  # bench.trace.Reduction


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_module(cell: Cell) -> ModuleType:
    kind = cell.traffic["kind"]
    return load_module(cell.bench_dir / "traffic" / f"{kind}.py",
                       f"bench_traffic_{kind}")


def metric_reader(cell: Cell, metric: str) -> ModuleType:
    return load_module(cell.bench_dir / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_"))


def _applies(metric: Dict, cell: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def resolve(spec: Dict, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``spec`` with its files loaded."""
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    entry = entries[name]
    workload = load_json(bench_dir / "workloads" / f"{name}.json")
    if workload["config"] != entry["config"]:
        raise ValueError(f"{name}: BENCHMARK.json names config "
                         f"{entry['config']!r}, the workload file "
                         f"{workload['config']!r}")
    if workload["traffic"]["mix"] != entry["traffic"]:
        raise ValueError(f"{name}: BENCHMARK.json names traffic "
                         f"{entry['traffic']!r}, the workload file "
                         f"{workload['traffic']['mix']!r}")
    config = load_json(bench_dir / "configs" / f"{entry['config']}.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                workload=workload, end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


class Spans:
    """The benchmark's own host spans, written into the profiler trace."""

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


def _start_trace(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python function events swamp the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, stamp: Optional[Dict] = None) -> Dict:
    """Set up, measure, check; the result line as a dict.

    ``stamp`` is the device stamp of ``device.require_tpu``; the tests
    pass their own to drive a run without a chip.
    """
    from bench import xplane as tr

    stamp = device.require_tpu(cell.chips) if stamp is None else stamp
    on_chip = stamp["platform"] == "tpu"
    compiles = device.CompileCounter()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        session = traffic_module(cell).Session(cell, seed)
        setup_s = time.monotonic() - t_start
        c0 = compiles.read()
        if trace:
            _start_trace(log_dir)
        try:
            spans = Spans()
            with spans.span("bench.window"):
                session.window(seconds, spans)
        finally:
            if trace:
                import jax

                jax.profiler.stop_trace()
        counters = dict(session.counters())
        counters["compiles"] = compiles.read() - c0
        e2e = session.end_to_end()
        mem = device.memory_peak_bytes(cell.chips) if on_chip else 0
        session.release()
        checks = session.checks()
        reduction = (tr.reduce_file(tr.find_xplane(log_dir))
                     if trace else None)
    finally:
        compiles.close()
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)

    dev = dict(stamp, memory_peak_bytes=mem)
    out: Dict = {"correct": bool(checks) and all(c.ok for c in checks),
                 "attempted": int(session.attempted),
                 "failed": int(session.failed)}
    if trace:
        readings = Readings(window_s=float(counters.get("window_s", seconds)),
                            counters=counters, reduction=reduction)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(cell, m["name"]).read(readings)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        out["metrics"] = metrics
        if reduction is not None:
            dev["busy_s"] = reduction.busy_s
            dev["window_s"] = reduction.window_s
            out["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduction.device_ops],
                "idle_gaps": [[n, s] for n, s in reduction.idle_gaps]}
    else:
        values = dict(e2e, setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = dev
    out["counters"] = counters
    out["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit}
                     for c in checks}
    return out


def _finite(x: float) -> Optional[float]:
    """JSON has no inf or NaN: a number that is not finite prints as null."""
    return float(x) if math.isfinite(x) else None


def emit(result: Dict) -> None:
    """The window's counters, then the checks as the last lines of stderr;
    the result as the last line of stdout."""
    print(f"counters: {json.dumps(result['counters'])}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import repro  # noqa: F401  (the system under test; fails without it)

    cell = resolve(load_json(SPEC_FILE), args.workload)
    try:
        stamp = device.require_tpu(cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  stamp))
    return 0
