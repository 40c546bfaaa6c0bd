"""(a) Every entry of BENCHMARK.json resolves to files that exist, and
every name, unit and line keeps to the contract's characters and lengths."""
import importlib
import json
import os
import re

import pytest

from bench_paths import ROOT
from benchmarks import manifest

BENCH = manifest.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(one_line(w) for w in BENCH["command"])
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("path", BENCH["paths"])
def test_paths_exist(path):
    assert os.path.isdir(os.path.join(ROOT, path))
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and one_line(conf["source"]) \
        and one_line(conf["why"])
    assert any(conf["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(os.path.join(ROOT, conf["file"])) as fh:
        body = json.load(fh)
    assert body["name"] == conf["name"] and body["source"] == conf["source"]
    assert len(conf["reduced"]) <= 16
    for key in conf["reduced"]:
        assert NAME.match(key) and key in body["reduced"], key
    for stated in ("guarantees", "assumed", "message_delay",
                   "protocol_instances", "ledger_age_at_window_open",
                   "shapes", "topology", "trace_seconds", "settings"):
        assert stated in body, stated
    # the settings are applied, so they have to be fields of the program's
    # Config; what differs from upstream's default is listed as assumed
    from plenum_tpu.config import load_config
    applied = load_config(body["settings"])
    assert all(getattr(applied, k) == v for k, v in body["settings"].items())
    assert any("Max3PCBatchWait" in a for a in body["assumed"])
    # the launcher kind is chosen by the file's `topology`, never by a name
    importlib.import_module(f"benchmarks.topologies.{body['topology']}")
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    loaded = manifest.cell(cell["name"])
    assert loaded["workload"]["name"] == cell["name"]
    drive = importlib.import_module(
        f"benchmarks.drives.{loaded['workload']['drive']}")
    assert drive.stream_length(loaded["workload"], 20.0) > 0
    if loaded["workload"]["drive"] == "open_loop":
        assert isinstance(loaded["workload"]["rate_per_s"], (int, float))
    generator = importlib.import_module(
        f"benchmarks.generators.{loaded['traffic']['kind']}")
    assert callable(generator.plan)
    e2e = manifest.metrics_of(cell["name"], "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert manifest.metrics_of(cell["name"], "per_layer")


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    spec = manifest.metric_spec("end_to_end", metric["name"])
    assert spec["name"] == metric["name"]
    reader = importlib.import_module(f"benchmarks.readers.{spec['kind']}")
    numbers = {"per_s": 1.0, "latency_p50_ms": 2.0, "latency_p95_ms": 3.0,
               "setup_s": 4.0}
    assert reader.read(spec, {"numbers": numbers}) in numbers.values()
    assert reader.read(spec, {"numbers": {}}) is None


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES and one_line(metric["layer"])
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    cells = {w["name"] for w in BENCH["workloads"]}
    for cell in metric.get("workloads", cells):
        assert cell in moved.get("workloads", cells), \
            f"{cell} does not report {moved['name']}"
    spec = manifest.metric_spec("per_layer", metric["name"])
    assert spec["name"] == metric["name"]
    reader = importlib.import_module(f"benchmarks.readers.{spec['kind']}")
    assert callable(reader.read)
    # a reader that finds nothing to read returns nothing
    assert reader.read(spec, {"counters": {"before": {}, "after": {}},
                              "samples": {}, "trace": None}) is None
    assert "roofline" not in metric["name"]


def test_peaks_unknown_device_is_an_error():
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        manifest.peaks("TPU v9 imaginary")
