"""Every file the harness finds by name loads, and every name and unit of
``BENCHMARK.json`` is made of the permitted characters."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
        assert set(m.get("workloads", [])) <= cells
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        mine = [m for m in BENCH["end_to_end"]
                if w["name"] in m.get("workloads", cells)]
        assert len(mine) >= 2, "setup_s and one more"
        assert any(w["name"] in m.get("workloads", cells)
                   for m in BENCH["per_layer"])
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    doc = json.loads((ROOT / cfg["file"]).read_text())
    for key in ("source", "guarantees", "reduced", "assumed", "corpus",
                "index", "index_name"):
        assert key in doc, key
    assert doc["source"] == cfg["source"]
    assert set(cfg["reduced"]) == set(doc["reduced"])
    kind = doc["corpus"]["kind"]
    assert (ROOT / "benchmarks" / "corpora" / f"{kind}.py").exists()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    from benchmarks import harness
    c = harness.Cell(cell["name"])
    assert hasattr(c.traffic_mod, "build") and c.traffic["streams"]
    for st in c.traffic["streams"]:
        assert st["arrivals"]["process"] in ("closed", "poisson")
        assert {"request", "queries"} <= set(st)
    assert c.spec["expected_lanes"] and c.spec["limits"]
    for m in c.metrics("per_layer"):
        assert harness.reader_file("layer_metrics", m["name"]).exists()
    for m in c.metrics("end_to_end"):
        assert harness.reader_file("end_metrics", m["name"]).exists()


def test_a_split_quantity_shares_one_reader():
    from benchmarks import harness
    a = harness.reader_file("layer_metrics", "device_idle_pct.tput")
    b = harness.reader_file("layer_metrics", "device_idle_pct.lat")
    assert a == b and a.name == "device_idle_pct.py"
    assert harness.reader_file("end_metrics", "qps").name == "qps.py"


def test_every_file_name_is_made_of_permitted_characters():
    for path in (ROOT / "benchmarks").rglob("*"):
        if "__pycache__" in path.parts or path.suffix == ".pyc":
            continue
        assert re.match(r"^[A-Za-z0-9_.\-]+$", path.name), path
