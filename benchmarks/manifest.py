"""BENCHMARK.json and the data files it names, found by name.

A later PR adds a deployment, a traffic mix, a cell or a per-layer metric
by adding a file here and an entry there; nothing in this module knows a
name."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return _load(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    """Everything one run needs: the manifest's entry for the cell, its
    workload file (drive), its configuration file and its traffic mix."""
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return {"entry": entry,
            "workload": _load(HERE, "workloads", name + ".json"),
            "config": _load(ROOT, conf["file"]),
            "traffic": _load(HERE, "traffic", entry["traffic"] + ".json")}


def metrics_of(name: str, group: str) -> list[dict]:
    """The manifest's `end_to_end` or `per_layer` metrics this cell reports."""
    return [m for m in benchmark()[group]
            if name in m.get("workloads", [name])]


SPEC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def metric_spec(group: str, name: str) -> dict:
    """What a metric reads and by which reader kind: a file of its own."""
    return _load(HERE, SPEC_DIRS[group], name + ".json")


def peaks(device_kind: str) -> dict:
    table = _load(HERE, "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"benchmark: no peaks for device kind {device_kind!r}"
                         f" in peaks.json")
    return table["devices"][device_kind]
