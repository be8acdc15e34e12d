"""A run with the timed path broken underneath reads ``correct: false``.

Each test drives ``bench.harness.run_cell`` on the CPU without the look
for a chip, on a cell's own traffic and checks at a small scale, with
one fault planted in the engine's executor. The unbroken run reads
``correct: true``; the control (the reference computed in bfloat16 in
the engine's place) reads above the cell's limit.
"""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SMALL = {"scale": 10}


@pytest.fixture
def exact_cell():
    cell = harness.resolve(harness.load_json(harness.SPEC_FILE),
                           "exact-g500-s18")
    cell.config.update(SMALL)
    return cell


def _run(cell, seed=2**31 + 11):
    return harness.run_cell(cell, seed, 0.5, False, time.monotonic(), CPU)


def test_sound_run_is_correct(exact_cell):
    out = _run(exact_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"exact_teps", "setup_s"}
    assert list(out)[-1] == "checks"


def _unchanged(orig):
    def step_sum(self, sources, valid, **kw):
        return np.zeros_like(orig(self, sources, valid, **kw))
    return step_sum


def _half_batch(orig):
    def step_sum(self, sources, valid, **kw):
        valid = np.asarray(valid, bool).copy()
        keep = np.flatnonzero(valid)[:max(1, int(valid.sum()) // 2)]
        half = np.zeros_like(valid)
        half[keep] = True
        return orig(self, sources, half, **kw) * (valid.sum() / half.sum())
    return step_sum


def _altered(orig):
    def step_sum(self, sources, valid, **kw):
        lam = orig(self, sources, valid, **kw).copy()
        top = int(np.argmax(lam))
        lam[top] *= 1.01
        return lam
    return step_sum


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_reads_incorrect(exact_cell, monkeypatch, fault):
    from repro.bc.executor import SingleHostExecutor

    monkeypatch.setattr(SingleHostExecutor, "step_sum",
                        fault(SingleHostExecutor.step_sum))
    out = _run(exact_cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_above_the_limit(exact_cell, seed):
    from bench.traffic import exact_sweep

    limit = exact_cell.limit("lam_gap")
    gap = exact_sweep.control_gap(exact_cell, seed, n_b=16)
    assert gap > 3 * limit, (gap, limit)


def test_every_seed_sweeps_the_same_work_in_another_order(exact_cell):
    from bench.traffic import exact_sweep

    sets = exact_sweep.batch_sources(233, 0, 16, 10)
    assert len(sets) == 10 and all(s.size == 16 for s in sets)
    flat = np.concatenate(sets)
    assert np.unique(flat).size == flat.size
    assert all(np.array_equal(a, b) for a, b in
               zip(sets, exact_sweep.batch_sources(233, 0, 16, 10)))
    orders = [exact_sweep.batch_order(10, s).tolist()
              for s in (1, 2, 2**31 + 3)]
    assert all(sorted(o) == list(range(10)) for o in orders)
    assert orders[0] != orders[1] != orders[2]
    with pytest.raises(ValueError):
        exact_sweep.batch_sources(159, 0, 16, 10)
