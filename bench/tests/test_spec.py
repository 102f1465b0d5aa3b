"""BENCHMARK.json resolves to its files and keeps to the benchmark's rules."""
import json
import os

import pytest

from bench import spec

BENCH = spec.load()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = spec.cell(BENCH, cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] == spec.config(BENCH, w["config"])["chips"] == 1
    t = spec.traffic(w["traffic"])
    assert t["name"] == w["traffic"]
    assert t["blocks_per_cycle"] % t["rounds_per_cycle"] == 0
    assert 0 < len(w["why"]) <= 200 and "\n" not in w["why"]
    reported = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end",
                                                    cell)}
    assert {"setup_s", "updates_per_s"} <= reported
    assert spec.metrics_for(BENCH, "per_layer", cell)


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    cfg = spec.config(BENCH, entry["name"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert key in cfg["source_values"]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    for text in (entry["why"], entry["source"]):
        assert 0 < len(text) <= 200 and "\n" not in text


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    assert callable(spec.reader(metric["name"]))
    assert 0 < len(metric["layer"]) <= 200
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    for cell in metric.get("workloads", []):
        assert cell in CELLS
        assert metric["moves"] in {m["name"] for m in spec.metrics_for(
            BENCH, "end_to_end", cell)}


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for name in names:
        assert spec.NAME.fullmatch(name), name
    for m in METRICS:
        assert spec.UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len({c["name"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m for m in BENCH["end_to_end"]}["setup_s"][
        "bound"] == 0.25


def test_peaks_table():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_file_is_small():
    path = os.path.join(spec.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        json.load(f)
