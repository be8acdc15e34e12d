"""The trace reduction, on a small trace recorded on one TPU v5e chip.

``data/small.xplane.pb`` was recorded by ``make_trace_fixture.py``:
three ``bench.step`` spans, each one jitted matmul, with 20 ms of host
sleep after each, inside one ``bench.window`` span. In this trace the
device timeline runs about 1 ms ahead of the host's, so the first
step's ops fall just before the window span opens and are clipped away.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import xplane  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small.xplane.pb")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_file(FIXTURE)


def test_reduction_of_the_recorded_trace(profile):
    r = xplane.reduce_profile(profile)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(65_464_106e-9, abs=1e-12)
    # two steps' ops inside the window: copy-start, copy-done, fusion each
    assert r.busy_s == pytest.approx((14 + 2 + 11_841 + 13 + 3 + 11_841)
                                     * 1e-9, abs=1e-12)
    assert 99.9 < r.idle_pct < 100.0
    assert r.device_ops[0] == ("%fusion fusion",
                               pytest.approx(2 * 11_841e-9, abs=1e-12))
    # the three sleeps are the longest gaps; the host was between steps
    assert [n for n, _ in r.idle_gaps[:3]] == ["bench.window"] * 3
    assert all(0.019 < s < 0.024 for _, s in r.idle_gaps[:3])
    assert r.span_busy["bench.step"][0] == 3


def test_busy_time_matches_a_brute_force_union(profile):
    devices, spans = xplane._events(profile)
    lo, hi = next((s, e) for n, s, e in spans if n == xplane.WINDOW_SPAN)
    evs = [(max(s, lo), min(e, hi)) for _, s, e in devices[0]
           if e > lo and s < hi]
    base = int(min(s for s, _ in evs))
    grid = np.zeros(int(max(e for _, e in evs)) - base + 1, bool)
    for s, e in evs:
        grid[int(s) - base:int(e) - base] = True
    r = xplane.reduce_profile(profile)
    assert r.busy_s == pytest.approx(grid.sum() * 1e-9, abs=1e-12)


def test_union_clip_and_overlap():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert xplane.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.overlap(merged, 1, 8) == 2 + 3
    assert xplane.overlap([], 0, 10) == 0.0


def test_self_times_subtract_nested_children():
    events = [("while", 0, 100), ("a", 10, 30), ("b", 40, 45),
              ("inner", 41, 44), ("c", 200, 210)]
    t = xplane.self_times(events)
    assert t == {"while": 75, "a": 20, "b": 2, "inner": 3, "c": 10}


def test_op_label_keeps_the_name_and_the_op():
    assert xplane.op_label(
        "%fusion.158 = f32[7626752,16]{1,0:T(8,128)} fusion(f32[16] %x), "
        "kind=kCustom") == "%fusion.158 fusion"
    assert xplane.op_label(
        "%while.24 = (f32[16]{0:T(128)}, s32[]{:T(128)}) while((f32[16], "
        "s32[]) %tuple.280), condition=%c") == "%while.24 while"
    assert xplane.op_label("plain") == "plain"


def test_no_window_span_reads_nothing(profile, monkeypatch):
    monkeypatch.setattr(xplane, "WINDOW_SPAN", "bench.absent")
    assert xplane.reduce_profile(profile) is None
