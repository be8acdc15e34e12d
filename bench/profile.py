"""Trace one window of a cell and print where its device time went.

  python bench/profile.py --workload <cell> --seed <n> --seconds <s> [--out <file.json>]

Run from the root of a checkout, on the chip, like ``bench/run.py``: the
same set-up and window, always traced, without the correctness check.
It prints, on stderr, device milliseconds per batch by the program's
scopes (``bench/scopes.py``), the per-iteration rung table of the
window's last batch, the ten longest idle gaps named by the innermost
``bench.*`` or ``repro.*`` span, and the executor and relax layers'
numbers; ``--out`` also writes them as JSON. The exact-sweep traffic
kind is the one it knows how to read counters from.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OCC_KEYS = ("overflows", "frontier_arcs", "arc_slots")  # window deltas


def _occ(session):
    return session.ex.occupancy_summary() or {}


def profile(cell, seed: int, seconds: float, stamp, t_start: float):
    """Set up ``cell``, trace one window, reduce the trace."""
    import jax
    from jax.profiler import ProfileData

    from bench import harness, scopes, xplane

    session = harness.traffic_module(cell).Session(cell, seed)
    setup_s = time.monotonic() - t_start
    occ0 = _occ(session)
    log_dir = tempfile.mkdtemp(prefix="bench-profile-")
    try:
        harness._start_trace(log_dir)
        try:
            spans = harness.Spans()
            with spans.span("bench.window"):
                session.window(seconds, spans)
        finally:
            jax.profiler.stop_trace()
        occ1 = _occ(session)
        path = xplane.find_xplane(log_dir)
        with open(path, "rb") as f:
            data = f.read()
        pd = ProfileData.from_file(path)
        r = scopes.reduce_profile(pd)
        if r is None:
            raise RuntimeError("the trace holds no device op inside the "
                               "window")
        lo, hi = scopes.window(pd)
        busy = scopes.scope_busy(pd, scopes.tf_ops(data), lo, hi)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    counters = dict(session.counters())
    counters.update({k: occ1.get(k, 0) - occ0.get(k, 0) for k in OCC_KEYS})
    batches = counters["batches"]
    step = r.span_busy.get("bench.step", (0, 0.0))
    program = busy.under("mfbf", "mfbr", "batch.")
    return {
        "workload": cell.name, "seed": seed, "device": stamp,
        "setup_s": setup_s, "counters": counters,
        "window_s": r.window_s, "busy_s": r.busy_s, "idle_pct": r.idle_pct,
        "step_device_ms": 1e3 * step[1] / step[0] if step[0] else None,
        "per_layer": scopes.per_layer(r, busy, counters),
        "scope_ms_per_batch": {k: 1e3 * v / batches for k, v in sorted(
            busy.by_path.items(), key=lambda kv: -kv[1])},
        "program_scope_share_pct": 100.0 * program / busy.total_s,
        "unattributed_pct": 100.0 * busy.unattributed_s / busy.total_s,
        "span_busy": {k: list(v) for k, v in r.span_busy.items()},
        "idle_gaps": [list(g) for g in r.idle_gaps],
        "gap_split": scopes.gap_split(pd),
        "device_ops": [list(o) for o in r.device_ops],
        "rows_bf": occ1.get("rows_bf"), "rows_br": occ1.get("rows_br"),
    }


def report(out) -> None:
    p = sys.stderr
    print(f"window {out['window_s']:.3f} s, busy {out['busy_s']:.3f} s, "
          f"idle {out['idle_pct']:.4f}%, setup {out['setup_s']:.1f} s",
          file=p)
    print(f"counters: {json.dumps(out['counters'])}", file=p)
    print(f"step_device_ms {out['step_device_ms']}", file=p)
    for k, v in out["per_layer"].items():
        print(f"{k} {v}", file=p)
    print(f"program scopes cover {out['program_scope_share_pct']:.3f}% of "
          f"device self time; no tf_op: {out['unattributed_pct']:.4f}%",
          file=p)
    print("device ms per batch by scope:", file=p)
    for k, v in out["scope_ms_per_batch"].items():
        print(f"  {v:10.3f}  {k}", file=p)
    for sweep in ("bf", "br"):
        print(f"last batch, {sweep}: [fnnz, rung, arcs] per relax", file=p)
        for i, row in enumerate(out[f"rows_{sweep}"] or []):
            print(f"  {i}: {row}", file=p)
    print("idle gaps, and the innermost host span through each:", file=p)
    for (name, s), (_, split) in zip(out["idle_gaps"], out["gap_split"]):
        parts = ", ".join(f"{k} {1e3 * v:.3f}" for k, v in sorted(
            split.items(), key=lambda kv: -kv[1]))
        print(f"  {1e3 * s:8.3f} ms  {name}: {parts}", file=p)


def main() -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    from bench import device, harness

    cell = harness.resolve(harness.load_json(harness.SPEC_FILE),
                           args.workload)
    try:
        stamp = device.require_tpu(cell.chips)
    except device.NoAccelerator as e:
        print(f"profile: {e}", file=sys.stderr)
        return 2
    out = profile(cell, args.seed, args.seconds, stamp, T_START)
    report(out)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
