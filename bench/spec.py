"""Resolve a cell of ``BENCHMARK.json`` to its files.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by its name:

    bench/configs/<config>.json    the deployment (sizes, path, guarantees)
    bench/traffic/<traffic>.json   the traffic mix, read by ``gen``
    bench/metrics/<metric>.py      a reader: ``read(reading) -> float | None``
    bench/peaks.json               the device's published peaks by kind

so a later change adds a cell or a metric by adding files and entries, with
no registry to edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    """The parsed ``BENCHMARK.json`` at ``root``."""
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            cfg = _load_json(os.path.join(root, c["file"]))
            if cfg["name"] != name:
                raise ValueError(f"{c['file']} names {cfg['name']!r}, "
                                 f"not {name!r}")
            return cfg
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def metrics_for(bench: dict, group: str, cell_name: str) -> list:
    """The entries of ``bench[group]`` that the cell reports: those without
    a ``workloads`` key, and those whose list names it."""
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict:
    """Published peaks of ``kind`` (``device.device_kind``); a kind that is
    not in the table is an error, not a default."""
    table = _load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]
