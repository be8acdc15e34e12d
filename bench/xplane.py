"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

Device time is the union of the intervals of the ops on each TPU's
``XLA Ops`` line. The window is the benchmark's own host span
``bench.window``; every number is clipped to it. Ops nest on that line
(a ``while`` holds the fusions of its body), so the top-ops list ranks
self time: an op's interval less its children's. Idle gaps are named by
the benchmark span that overlaps them most (the innermost on a tie),
which says what the host was doing while the chip waited:
``bench.window`` for a gap between the benchmark's inner spans.

The host and device timelines of a trace agree to about a millisecond,
so a reading over a window of seconds is exact to a few parts in 1e4.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over devices of busy time inside the window
    n_devices: int
    device_ops: List[Tuple[str, float]]  # top ops by device seconds
    idle_gaps: List[Tuple[str, float]]  # longest gaps, named by host span
    span_busy: Dict[str, Tuple[int, float]]  # span -> (count, device s)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` covered by the merged intervals."""
    if not merged:
        return 0.0
    starts = np.fromiter((s for s, _ in merged), float, len(merged))
    i = max(int(np.searchsorted(starts, lo, side="right")) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


def op_label(name: str) -> str:
    """``%fusion.158 fusion`` from the op's full HLO text."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def self_times(events: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per-name self time of properly nested (name, start, end) events."""
    totals: Dict[str, float] = {}
    stack: List[list] = []

    def pop() -> None:
        name, s, e, children = stack.pop()
        totals[name] = totals.get(name, 0.0) + max(0.0, e - s - children)

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            pop()
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        pop()
    return totals


def _events(pd):
    """(devices, spans): per-device op events and the bench host spans."""
    devices: Dict[int, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name in OPS_LINES:
                    evs.extend((e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def reduce_profile(pd) -> Optional[Reduction]:
    """Reduce a ``jax.profiler.ProfileData``; None without a window span
    or without any device op inside it."""
    devices, spans = _events(pd)
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    window_ns = hi - lo
    busy = {d: union(clip([(s, e) for _, s, e in evs], lo, hi))
            for d, evs in devices.items()}
    busy_ns = [sum(e - s for s, e in iv) for iv in busy.values()]
    if window_ns <= 0 or not any(busy_ns):
        return None
    n_dev = len(devices)

    per_op: Dict[str, float] = {}
    for evs in devices.values():
        clipped = [(op_label(n), max(s, lo), min(e, hi)) for n, s, e in evs
                   if e > lo and s < hi]
        for name, t in self_times(clipped).items():
            per_op[name] = per_op.get(name, 0.0) + t
    ops = sorted(((k, v / n_dev * 1e-9) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:TOP]

    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    span_busy: Dict[str, Tuple[int, float]] = {}
    for name, s, e in inner:
        if e <= lo or s >= hi:
            continue
        dev_s = sum(overlap(iv, s, e) for iv in busy.values()) / n_dev
        cnt, tot = span_busy.get(name, (0, 0.0))
        span_busy[name] = (cnt + 1, tot + dev_s * 1e-9)

    first = busy[min(busy)]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, best_key = "no span", (0.0, 0.0)
        for name, hs, he in inner + [(WINDOW_SPAN, lo, hi)]:
            ov = min(e, he) - max(s, hs)
            key = (ov, -(he - hs))
            if ov > 0 and key > best_key:
                best, best_key = name, key
        named.append((best, (e - s) * 1e-9))

    return Reduction(window_s=window_ns * 1e-9,
                     busy_s=sum(busy_ns) / n_dev * 1e-9, n_devices=n_dev,
                     device_ops=ops, idle_gaps=named, span_busy=span_busy)


def reduce_file(path: str) -> Optional[Reduction]:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
