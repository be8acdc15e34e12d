"""What ties a run to its device: the peaks table, device-checked
calibrations, the placeable compile cache, and the chip smoke script's
refusal to run without a TPU."""
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.bc import BCPlanner, BCQuery
from repro.graphs.generators import path_graph, rmat
from repro.launch import runtime
from repro.spgemm.cost_model import (DEVICE_PEAKS, TARGET_KIND, Calibration,
                                     StepRates, device_peaks,
                                     load_calibration, save_calibration)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = ("tpu", "TPU v5 lite")


# ------------------------------------------------------------------ peaks
def test_peaks_table_is_keyed_by_device_kind_with_a_source():
    peaks = device_peaks("TPU v5 lite", "tpu")
    assert peaks is DEVICE_PEAKS["TPU v5 lite"]
    assert peaks.hbm_bw == 819e9 and peaks.bf16_flops == 197e12
    assert "TPU v5e" in peaks.source


def test_unknown_tpu_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="TPU v9"):
        device_peaks("TPU v9", "tpu")


def test_non_tpu_backend_prices_the_design_target():
    assert device_peaks("cpu", "cpu") is DEVICE_PEAKS[TARGET_KIND]
    assert device_peaks() is DEVICE_PEAKS[TARGET_KIND]  # tests run on CPU


# ------------------------------------------------------------ calibration
def _cal(meta):
    return Calibration(rates={"dense": StepRates(1e9), "coo": StepRates(1e12),
                              "csr": StepRates(1e6)}, meta=meta)


@pytest.mark.parametrize("meta,used", [
    ({"platform": "tpu", "device_kind": "TPU v5 lite"}, True),
    ({"platform": "cpu", "device_kind": "cpu"}, False),
    ({"platform": "tpu", "device_kind": "TPU v4"}, False),
    ({"jax_backend": "cpu"}, False),  # records no device at all
])
def test_calibration_is_used_only_on_the_device_that_measured_it(
        tmp_path, meta, used):
    path = save_calibration(_cal(meta), str(tmp_path / "cal.json"))
    cal, note = load_calibration(path, device=V5E)
    assert (cal is not None) is used
    assert (note is None) is used
    if not used:
        assert "not used" in note and "tpu/TPU v5 lite" in note


def test_absent_calibration_is_silent(tmp_path):
    assert load_calibration(str(tmp_path / "none.json"), device=V5E) == \
        (None, None)


def test_refused_calibration_reaches_the_plan_notes(tmp_path, monkeypatch):
    """A calibration another device measured must not route this run,
    and the plan must say why it was ignored."""
    path = save_calibration(_cal({"platform": "tpu",
                                  "device_kind": "TPU v4"}),
                            str(tmp_path / "cal.json"))
    monkeypatch.setenv("REPRO_BC_CALIBRATION", path)
    g = rmat(6, 8, seed=0)
    pl = BCPlanner().plan(g, BCQuery(mode="exact"), n_devices=1)
    assert pl.regime["calibrated"] is False
    assert any("not used" in note for note in pl.notes)
    # the same file measured on this (CPU) device routes the plan
    save_calibration(_cal({"platform": "cpu",
                           "device_kind": jax.devices()[0].device_kind}),
                     path)
    pl = BCPlanner().plan(g, BCQuery(mode="exact"), n_devices=1)
    assert pl.regime["calibrated"] is True
    assert not pl.notes


def test_calibrate_records_the_device():
    from repro.launch.calibrate import calibrate

    g = rmat(5, 4, seed=0)
    cal = calibrate(g, nb_pair=(8, 16), reps=1, variants=(("coo", False),))
    dev = jax.devices()[0]
    assert cal.meta["platform"] == dev.platform
    assert cal.meta["device_kind"] == dev.device_kind


# ---------------------------------------------------------- compile cache
def test_compile_cache_env_wins_and_sets_nothing(monkeypatch, tmp_path,
                                                 restore_compile_cache):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_compile_cache):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    path = runtime.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.enable_compile_cache() == path  # fixed, not per call


# ------------------------------------------------------------- chip smoke
@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    return chip_smoke


def test_chip_smoke_fails_without_a_tpu(chip_smoke, capsys,
                                        restore_compile_cache):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert "no TPU" in out[-1]
    with pytest.raises(json.JSONDecodeError):
        json.loads(out[-1])


@pytest.mark.parametrize("n,source,ecc", [(9, 0, 8), (9, 4, 4)])
def test_chip_smoke_hop_eccentricity(chip_smoke, n, source, ecc):
    g = path_graph(n)
    assert chip_smoke.hop_eccentricity(g, np.array([source])) == ecc
