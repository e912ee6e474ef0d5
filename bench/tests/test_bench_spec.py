"""``BENCHMARK.json`` and the files it names: every cell, configuration,
driver, reference and metric is found by name, and every name and unit keeps
to the benchmark's character rules.  A fixture cell shows that adding one
needs only new files."""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from bench import harness, peaks

ROOT = harness.ROOT
BENCH = harness.BENCH
BENCHMARK = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
WORKLOAD_FILES = sorted((BENCH / "workloads").glob("*.json"))


def _names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCHMARK[key]:
            yield entry["name"]
    for w in BENCHMARK["workloads"]:
        yield w["config"]
        yield w["traffic"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert NAME.match(name)


@pytest.mark.parametrize("metric", BENCHMARK["end_to_end"] + BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_units_and_directions(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")


@pytest.mark.parametrize("path", WORKLOAD_FILES, ids=lambda p: p.stem)
def test_workload_files_name_existing_files(path):
    w = json.loads(path.read_text())
    config = BENCH / "configs" / f"{w['config']}.json"
    assert config.is_file()
    assert (BENCH / "drivers" / f"{w['driver']}.py").is_file()
    ref = json.loads(config.read_text())["reference"]
    assert (BENCH / "references" / f"{ref}.py").is_file()
    assert w["chips"] in (1, 4) and w["limits"] and w["trace_seconds"] > 0


@pytest.mark.parametrize("entry", BENCHMARK["workloads"], ids=lambda w: w["name"])
def test_benchmark_cells_match_their_files(entry):
    w = json.loads((BENCH / "workloads" / f"{entry['name']}.json").read_text())
    for key in ("config", "traffic", "chips", "why"):
        assert w[key] == entry[key]
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("entry", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_configs_name_their_files(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"] and cfg["sites"] == cfg["L"] ** 4


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e, per_layer = harness.metrics_for(BENCHMARK, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and per_layer
    assert all(m["moves"] in names for m in per_layer)
    for m in per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in BENCHMARK["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert f"`{layer}`" in perf


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_host_without_a_tpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{\"correct\"") for line in proc.stdout.splitlines())


def test_run_fails_without_the_program(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, nothing runs."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


FIXTURE_DRIVER = '''
from bench.harness import Window


def setup(cell, previous=None):
    return {"n": cell.params["n"]}


def window(state, seconds, trace):
    return Window(seconds=seconds, attempted=state["n"], failed=0,
                  metrics={"things_per_s": state["n"] / seconds},
                  counters={"seen": state["n"]}, kept={"x": 2.0})


def release(state, win):
    pass


def checks(state, win):
    return {"gap": abs(win.kept["x"] - 2.0)}


def control(state, win):
    return {"gap": 1.0}
'''


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell, configuration, driver, reference and per-layer metric added as
    files, and found by name, with no edit to the harness."""
    base = tmp_path / "bench"
    for d in ("configs", "workloads", "drivers", "metrics", "references"):
        (base / d).mkdir(parents=True)
    (base / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "reference": "toy_ref", "L": 2, "reduced": []}))
    (base / "references" / "toy_ref.py").write_text("")
    (base / "workloads" / "toy.count.json").write_text(json.dumps(
        {"config": "toy", "traffic": "count", "driver": "toy_driver", "chips": 1,
         "why": "fixture", "params": {"n": 7}, "trace_seconds": 1,
         "limits": {"gap": 0.5}}))
    (base / "drivers" / "toy_driver.py").write_text(FIXTURE_DRIVER)
    (base / "metrics" / "seen.toy.py").write_text(
        "def read(record):\n    return record.counters['seen']\n")
    benchmark = {
        "end_to_end": [
            {"name": "things_per_s", "unit": "1/s", "workloads": ["toy.count"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "seen.toy", "unit": "count", "moves": "things_per_s",
                       "workloads": ["toy.count"]}],
    }

    class Device:
        platform, device_kind = "fixture", "fixture"

        @staticmethod
        def memory_stats():
            return {"peak_bytes_in_use": 1}

    cell = harness.load_cell("toy.count", 5, base=base)
    out = harness.run_cell(cell, 2.0, False, devices=[Device()], peaks=None,
                           benchmark=benchmark, started_s=0.0)
    assert out["correct"] and out["attempted"] == 7
    assert out["metrics"]["things_per_s"]["value"] == 3.5
    assert set(out["metrics"]) == {"things_per_s", "setup_s"}
    assert list(out)[-1] == "checks" and out["checks"]["gap"]["limit"] == 0.5

    rec = harness.Record(cell=cell, trace=None, counters={"seen": 7}, peaks=None)
    assert cell.module("metrics", "seen.toy").read(rec) == 7


def test_peaks_cover_the_chip_the_cells_ask_for():
    assert peaks.for_device_kind("TPU v5 lite").hbm_bytes_per_s > 0
