"""Find a cell's configuration, traffic, driver, reference and per-layer
metric readers by the names in BENCHMARK.json.

    configs/<config>.json      sizes of the configuration, with "kind" and
                               the path of its plain "reference"
    traffic/<traffic>.json     parameters of the mix, with its "driver"
    drivers/<driver>.py        set-up, window and check for that kind
    metrics/<metric>.py        read(run) -> float | None, one per metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]


def load_module(path: pathlib.Path, name: str):
    """Import a file of the benchmark by path (names may hold '.' or '-')."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]      # metrics this cell reports with --trace 0
    per_layer: list[dict]       # metrics this cell reports with --trace 1

    def driver(self):
        return load_module(HERE / "drivers" / f"{self.traffic['driver']}.py",
                           f"chipbench_driver_{self.traffic['driver']}")

    def reference(self):
        return load_module(HERE / self.config["reference"],
                           f"chipbench_ref_{self.config['kind']}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: pathlib.Path) -> Cell:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file}")
    w = cells[name]
    config = json.loads((HERE / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in moved and _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def metric_reader(name: str):
    """The `read(run)` function of per-layer metric `name`."""
    return load_module(HERE / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name}").read
