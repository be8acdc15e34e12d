"""Device time by the program's own scopes, and idle gaps named by its spans.

The program wraps each stage of a batch in ``jax.named_scope`` (``mfbf``,
``mfbr``, ``init``, ``relax.pick``, ``relax.rung<i>``,
``relax.full_edge``, ``update``, ``batch.reduce``, ...), and its host
code in ``repro.*`` profiler spans. A scope reaches the trace only as a
component of the op's ``tf_op`` stat, which ``jax.profiler.ProfileData``
does not expose; ``tf_ops`` reads it from the ``.xplane.pb`` wire format
(``XSpace.planes`` = 1; ``XPlane.name`` = 2, ``event_metadata`` = 4,
``stat_metadata`` = 5, map entries key 1 / value 2;
``XEventMetadata.name`` = 2, ``stats`` = 5; ``XStatMetadata.name`` = 2;
``XStat.metadata_id`` = 1, ``str_value`` = 5, ``ref_value`` = 7). The
metadata name is the op's HLO text, which is also the event's name in
``ProfileData``, so the two join on it. A fusion carries its root's
``tf_op``.

``python3 bench/profile.py`` runs a cell traced and prints these tables;
nothing in ``bench/run.py`` reads them yet.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Tuple
from unittest import mock

from bench import xplane

PROGRAM_SPAN_PREFIX = "repro."
# The program's scope names; the other components of a ``tf_op`` are
# JAX's own (``jit(f)``, ``while``, ``body``, ``cond``, ``branch_1_fun``)
# or the op's primitive.
SCOPE = re.compile(r"^(mfbf|mfbr|init|update|batch\.\w+|relax\.\w+)$")


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _message(buf: memoryview) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for num, val in _fields(buf):
        out.setdefault(num, []).append(val)
    return out


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def tf_ops(data: bytes) -> Dict[str, str]:
    """HLO op text → ``tf_op`` for every op on a TPU plane of a serialized
    ``XSpace``; ops without the stat are left out."""
    out: Dict[str, str] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        p = _message(plane)
        if not xplane.DEVICE_PLANE.match(_text(p.get(2, [b""])[0])):
            continue
        stat_names = {}
        for entry in p.get(5, []):
            md = _message(_message(entry)[2][0])
            stat_names[md.get(1, [0])[0]] = _text(md.get(2, [b""])[0])
        tf_id = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        if tf_id is None:
            continue
        for entry in p.get(4, []):
            ev = _message(_message(entry)[2][0])
            for stat in ev.get(5, []):
                st = _message(stat)
                if st.get(1, [None])[0] != tf_id:
                    continue
                if 5 in st:
                    op = _text(st[5][0])
                else:
                    op = stat_names.get(st.get(7, [None])[0], "")
                if op:
                    out[_text(ev.get(2, [b""])[0])] = op
    return out


@dataclasses.dataclass
class ScopeBusy:
    """Device self seconds inside the window, mean over devices."""

    by_component: Dict[str, float]  # each ``tf_op`` component -> s
    by_path: Dict[str, float]  # the program's scopes, joined -> s
    unattributed_s: float  # ops with no ``tf_op``
    total_s: float  # every op

    def under(self, *prefixes: str) -> float:
        """Seconds of the ops with a program scope that starts with one
        of ``prefixes`` (``"relax."``, ``"mfbf"``)."""
        return sum(s for path, s in self.by_path.items()
                   if any(c.startswith(prefixes) for c in path.split("/")))


def scope_busy(pd, ops: Dict[str, str], lo: float, hi: float) -> ScopeBusy:
    """Per-scope device self time of ``pd`` inside ``[lo, hi)`` ns, with
    ``ops`` from ``tf_ops``."""
    devices, _ = xplane._events(pd)
    per_op: Dict[str, float] = {}
    for evs in devices.values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                   if e > lo and s < hi]
        for name, t in xplane.self_times(clipped).items():
            per_op[name] = per_op.get(name, 0.0) + t
    n_dev = max(len(devices), 1)
    comps: Dict[str, float] = {}
    paths: Dict[str, float] = {}
    none = total = 0.0
    for name, ns in per_op.items():
        s = ns / n_dev * 1e-9
        total += s
        op = ops.get(name)
        if not op:
            none += s
            continue
        parts = op.split("/")
        for c in dict.fromkeys(parts):
            comps[c] = comps.get(c, 0.0) + s
        path = "/".join(c for c in parts if SCOPE.match(c)) or "(none)"
        paths[path] = paths.get(path, 0.0) + s
    return ScopeBusy(comps, paths, none, total)


def reduce_profile(pd) -> Optional[xplane.Reduction]:
    """``xplane.reduce_profile`` with the program's ``repro.*`` spans
    beside the benchmark's: ``span_busy`` holds them too, and an idle gap
    is named by the innermost span around it."""
    with mock.patch.object(xplane, "SPAN_PREFIX",
                           (xplane.SPAN_PREFIX, PROGRAM_SPAN_PREFIX)):
        return xplane.reduce_profile(pd)


def gap_split(pd, top: int = xplane.TOP
              ) -> List[Tuple[float, Dict[str, float]]]:
    """The ``top`` longest idle gaps of the first device inside the
    window, each as (seconds, {span: seconds}): how long each
    ``bench.*`` or ``repro.*`` span was the innermost one open on the
    host during the gap. A gap between two batches straddles the end of
    one step and the start of the next, so no single span holds it; this
    says which part of the host's work it was spent in."""
    with mock.patch.object(xplane, "SPAN_PREFIX",
                           (xplane.SPAN_PREFIX, PROGRAM_SPAN_PREFIX)):
        devices, spans = xplane._events(pd)
    win = window(pd)
    if win is None or not devices:
        return []
    lo, hi = win
    busy = xplane.union(xplane.clip(
        [(s, e) for _, s, e in devices[min(devices)]], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        open_ = [(n, hs, he) for n, hs, he in spans if he > s and hs < e]
        cuts = sorted({s, e} | {x for _, hs, he in open_ for x in (hs, he)
                                if s < x < e})
        split: Dict[str, float] = {}
        for a, b in zip(cuts, cuts[1:]):
            inner = [(he - hs, n) for n, hs, he in open_ if hs <= a and he >= b]
            name = min(inner)[1] if inner else "no span"
            split[name] = split.get(name, 0.0) + (b - a) * 1e-9
        out.append(((e - s) * 1e-9, split))
    return out


def window(pd) -> Optional[Tuple[float, float]]:
    """(start, end) ns of the ``bench.window`` span, if the trace has one."""
    _, spans = xplane._events(pd)
    return next(((s, e) for n, s, e in spans if n == xplane.WINDOW_SPAN),
                None)


def per_layer(r: Optional[xplane.Reduction], busy: Optional[ScopeBusy],
              counters: Dict) -> Dict[str, Optional[float]]:
    """The executor and relax layers' numbers over a traced window; None
    where the trace or the program's counters do not hold them.

    ``executor_device_ms``: device busy per ``repro.executor.step`` span;
    ``relax_device_ms``: device self time per batch under any ``relax.*``
    scope, both sweeps; ``full_edge_device_ms``: its part under
    ``relax.full_edge``; ``relax_arc_yield``: 100 × frontier arcs over
    arc slots processed (useful arcs per arc slot), from the window's
    deltas of the executor's occupancy counters.
    """
    step = r.span_busy.get("repro.executor.step") if r is not None else None
    batches = counters.get("batches") or 0
    relax = busy.under("relax.") if busy is not None else 0.0
    slots = counters.get("arc_slots") or 0
    return {
        "executor_device_ms": (1e3 * step[1] / step[0]
                               if step and step[0] and step[1] > 0
                               else None),
        "relax_device_ms": 1e3 * relax / batches if relax and batches
        else None,
        "full_edge_device_ms": (1e3 * busy.under("relax.full_edge")
                                / batches if relax and batches else None),
        "relax_arc_yield": (100.0 * counters.get("frontier_arcs", 0) / slots
                            if slots else None),
    }
