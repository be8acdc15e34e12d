"""``BENCHMARK.json`` and the files it names, on the CPU.

Every cell, configuration, traffic kind and per-layer metric is found by
name; a dummy of each, added as files of its own, is found and run the
same way; names, units and links between metrics keep to the rules.
"""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import device, harness  # noqa: E402

SPEC = harness.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0].startswith("python")
    assert (ROOT / SPEC["command"][1]).is_file()
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_and_units_keep_to_the_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
                assert entry["source"] in SOURCES
    for cfg in SPEC["configs"]:
        assert all(NAME.match(k) for k in cfg["reduced"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    assert len(set(names)) == len(names)


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_with_its_files(cell):
    c = harness.resolve(SPEC, cell)
    assert c.config["name"] == c.workload["config"]
    assert (ROOT / "bench" / "configs" / f"{c.config['name']}.json").is_file()
    assert hasattr(harness.traffic_module(c), "Session")
    assert c.workload["checks"], "a cell needs numbers that decide correct"
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(harness.metric_reader(c, m["name"]).read)


def test_every_config_is_used_and_its_file_lies_under_paths():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for cfg in SPEC["configs"]:
        assert cfg["name"] in used
        assert any(cfg["file"].startswith(p + "/") for p in SPEC["paths"])
        assert harness.load_json(ROOT / cfg["file"])["name"] == cfg["name"]
        files.add(cfg["file"])
    assert len(files) == len(SPEC["configs"])


def test_moves_names_a_metric_every_cell_of_the_metric_reports():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in target or cell in target["workloads"]
        assert m["layer"] and "\n" not in m["layer"]


def _dummy_tree(tmp: Path) -> dict:
    """A cell, config, traffic kind and metric that exist only as files."""
    for sub in ("configs", "workloads", "traffic", "metrics"):
        (tmp / sub).mkdir(parents=True)
    (tmp / "configs" / "toy.json").write_text(json.dumps({"name": "toy"}))
    (tmp / "workloads" / "toy.count.json").write_text(json.dumps({
        "config": "toy", "traffic": {"kind": "counting", "mix": "ticks"},
        "checks": {"off_by": 0}}))
    (tmp / "traffic" / "counting.py").write_text(
        "from bench.harness import Check\n"
        "class Session:\n"
        "    attempted = failed = 0\n"
        "    def __init__(self, cell, seed):\n"
        "        self.cell, self.n = cell, seed % 7\n"
        "    def window(self, seconds, spans):\n"
        "        with spans.span('bench.step'):\n"
        "            self.attempted = self.n + 3\n"
        "    def end_to_end(self):\n"
        "        return {'ticks_per_s': float(self.attempted)}\n"
        "    def counters(self):\n"
        "        return {'ticks': self.attempted, 'window_s': 1.0}\n"
        "    def release(self):\n"
        "        pass\n"
        "    def checks(self):\n"
        "        return [Check('off_by', 0.0, self.cell.limit('off_by'))]\n")
    (tmp / "metrics" / "ticks.toy.py").write_text(
        "def read(readings):\n"
        "    return readings.counters['ticks'] * 2\n")
    (tmp / "metrics" / "silent.py").write_text(
        "def read(readings):\n    return None\n")
    return {
        "workloads": [{"name": "toy.count", "config": "toy",
                       "traffic": "ticks", "chips": 1, "why": "dummy"}],
        "end_to_end": [
            {"name": "ticks_per_s", "unit": "1/s", "better": "higher",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "ticks.toy", "unit": "ticks", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "ticks_per_s"},
            {"name": "silent", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "toy",
             "moves": "ticks_per_s"},
            {"name": "elsewhere", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "toy",
             "moves": "ticks_per_s", "workloads": ["other"]}],
    }


def test_a_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    spec = _dummy_tree(tmp_path)
    cell = harness.resolve(spec, "toy.count", bench_dir=tmp_path)
    assert [m["name"] for m in cell.per_layer] == ["ticks.toy", "silent"]
    t0 = time.monotonic()
    plain = harness.run_cell(cell, 12, 1.0, False, t0, CPU)
    assert plain["correct"] and plain["attempted"] == 8
    assert set(plain["metrics"]) == {"ticks_per_s", "setup_s"}
    traced = harness.run_cell(cell, 12, 1.0, True, t0, CPU)
    # a reader that finds nothing leaves its metric out of the line
    assert traced["metrics"] == {"ticks.toy": {"value": 16.0,
                                               "unit": "ticks"}}
    assert list(traced)[-1] == "checks"


def test_mismatched_traffic_name_is_refused(tmp_path):
    spec = _dummy_tree(tmp_path)
    spec["workloads"][0]["traffic"] = "other"
    with pytest.raises(ValueError):
        harness.resolve(spec, "toy.count", bench_dir=tmp_path)
    with pytest.raises(KeyError):
        harness.resolve(spec, "missing", bench_dir=tmp_path)


def test_peaks_are_published_and_unknown_kinds_fail():
    p = device.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError):
        device.peaks("TPU v99")


def test_compile_counter_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    counter = device.CompileCounter()
    before = counter.read()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    assert counter.read() > before


def _run_bench(cwd: Path, env_extra: dict):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "exact-g500-s18",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing(tmp_path):
    r = _run_bench(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode != 0 and r.stdout == ""
    assert "not a TPU" in r.stderr


def test_run_from_the_benchmark_files_alone_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_bench(tmp_path, {})
    assert r.returncode != 0 and r.stdout == ""
    assert "No module named 'repro'" in r.stderr
