"""Every cell, configuration and metric of BENCHMARK.json resolves to its
files by name, and the file keeps to the benchmark's contract."""
import json
import re

import pytest

import cells
from harness import spec

BENCH = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith("benchmarks/chip/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = spec.load_cell(cell, cells.ROOT / "BENCHMARK.json")
    assert (spec.HERE / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert c.reference().__doc__
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_named_and_holds_its_source(cfg):
    path = cells.ROOT / cfg["file"]
    assert path == spec.HERE / "configs" / f"{cfg['name']}.json"
    data = json.loads(path.read_text())
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert (spec.HERE / data["reference"]).is_file()
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert key in data or key in data.get("changed_from_source", {})


def test_names_units_and_links():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for w in m["workloads"]:
            assert "workloads" not in e2e[m["moves"]] \
                or w in e2e[m["moves"]]["workloads"]
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        layers.setdefault(m["layer"], 0)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_four_chip_cells_at_most_half():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
