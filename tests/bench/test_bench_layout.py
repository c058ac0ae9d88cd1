"""BENCHMARK.json against the contract's shape, and every configuration,
traffic mix, generator and metric reader it names, found by name."""

import json
import re

import pytest

from bench import harness
from bench.configs.models import CONFIG_DIR, load_config
from bench.traffic.common import load_traffic

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [c["name"] for c in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run_cell.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert (harness.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (harness.ROOT / p).is_dir()


def test_names_units_and_sources():
    entries = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
        + BENCH["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    assert len(METRICS) == len(set(METRICS))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m["workloads"]) <= set(CELLS) if "workloads" in m else 1
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in CELLS:
        own = {m["name"] for m in harness.metrics_for(BENCH, cell, False)}
        assert "setup_s" in own and len(own) >= 2
        layer = harness.metrics_for(BENCH, cell, True)
        assert layer
        for m in layer:
            assert m["moves"] in own, (cell, m["name"])


def test_free_text_fits_on_one_line():
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
        assert "\t" not in e["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200


def test_per_layer_layers_are_one_name_per_layer():
    for m in BENCH["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_its_config_traffic_and_generator(cell):
    spec = harness.workload(BENCH, cell)
    assert spec["chips"] == 1
    config = load_config(spec["config"])
    assert config["name"] == spec["config"]
    traffic = load_traffic(spec["traffic"])
    assert hasattr(harness.load_generator(traffic["kind"]), "build")
    assert hasattr(harness.load_reference(config["reference"]),
                   "class_sums")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_files_match_their_entries(entry):
    path = harness.ROOT / entry["file"]
    assert path.parent == CONFIG_DIR
    config = json.loads(path.read_text())
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"] == []
    assert config["source"] == entry["source"]
    assert config["n_includes"] <= (config["n_classes"] * config["n_clauses"]
                                    * 2 * config["n_features"])
