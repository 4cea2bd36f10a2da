"""BENCHMARK.json against the rules the harness relies on: every name
resolves to its files, names and units use the allowed characters, and
each per-layer metric's cells report the end-to-end metric it moves."""

from __future__ import annotations

import json
import re

import pytest

import bench_cells  # noqa: F401  (puts bench/ on sys.path)
from harness import HERE, ROOT, Cell, load_benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def bench():
    return load_benchmark()


def test_keys_and_limits(bench):
    assert set(bench) == KEYS["top"]
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for kind, key in (("config", "configs"), ("workload", "workloads"),
                      ("end_to_end", "end_to_end"),
                      ("per_layer", "per_layer")):
        for entry in bench[key]:
            extra = set(entry) - KEYS[kind] - {"workloads"}
            assert not extra and KEYS[kind] <= set(entry), (key, entry)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_and_units(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_workload_resolves_by_name(bench):
    for w in bench["workloads"]:
        cell = Cell.resolve(bench, w["name"])
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert conf["file"] == f"bench/configs/{w['config']}.json"
        data = json.loads((ROOT / conf["file"]).read_text())
        assert data["name"] == conf["name"] and data["source"] == conf["source"]
        assert data["reduced"] == conf["reduced"]
        for k in data["reduced"]:
            assert k in data["model"] or k in data, k
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert callable(cell.driver.run)
        for m in cell.per_layer:
            assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    for c in bench["configs"]:
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_each_cell_reports_what_its_metrics_move(bench):
    for w in bench["workloads"]:
        cell = Cell.resolve(bench, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        for other, name in layers.items():
            if other.lower() == m["layer"].lower():
                assert other == m["layer"]
        layers[m["layer"]] = m["name"]
    roofline = [m for m in bench["per_layer"] if m["name"].endswith("roofline")]
    for k in roofline:
        # a kernel's roofline share stands beside a whole step's mfu that
        # moves the same end-to-end metric
        assert any("mfu" in m["name"] and m["moves"] == k["moves"]
                   for m in bench["per_layer"]), k["name"]


def test_run_seconds_fit_the_check(bench):
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (bench["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
