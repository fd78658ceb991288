"""Cells of the benchmark cut to a size a CPU test run can hold: the same
drivers, references and checks as on the chip, at small sizes."""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec   # noqa: E402

GRID = {"recordcount": 2048}
GRID_MIX = {"operationcount": 512}
# at these widths a random model with tied embeddings repeats its last
# token, which would hide a decode step that returns its input; the test
# cells untie them
DECODER = {"hidden_size": 128, "intermediate_size": 1024,
           "num_hidden_layers": 4, "num_attention_heads": 4,
           "num_key_value_heads": 2, "vocab_size": 512,
           "tie_word_embeddings": False}
SERVE = {"rate_per_s": 8.0, "prompt_tokens": 32, "new_tokens": 6,
         "max_batch": 2, "trace_seconds": 0.5, "check_requests": 4}


def _merge(a: dict, b: dict) -> dict:
    out = copy.deepcopy(a)
    for k, v in b.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) else v
    return out


# cells whose files are in the tree but not yet in BENCHMARK.json
KEPT = {"phi4mini-kv-reuse": ("phi4mini-prefix-kv", "phi4mini-reuse")}
KEPT_E2E = [{"name": "setup_s", "unit": "s"},
            {"name": "serve_p50_ms", "unit": "ms"},
            {"name": "serve_p90_ms", "unit": "ms"}]


def _load(kind: str, name: str) -> dict:
    return json.loads((HERE / kind / f"{name}.json").read_text())


def small_cell(name: str, traffic: str | None = None) -> spec.Cell:
    """The named cell at test size (from BENCHMARK.json, or from its files
    for a cell kept for later), with another traffic file of the same
    driver in place of its own if `traffic` names one."""
    try:
        cell = spec.load_cell(name, ROOT / "BENCHMARK.json")
    except KeyError:
        config, mix = KEPT[name]
        cell = spec.Cell(name, 1, _load("configs", config),
                         _load("traffic", mix), KEPT_E2E, [])
    if traffic is not None:
        cell.traffic = _load("traffic", traffic)
    if cell.config["kind"] == "replay_grid":
        cell.config = _merge(cell.config, GRID)
        cell.traffic = _merge(cell.traffic, GRID_MIX)
    else:
        cell.config = _merge(cell.config, DECODER)
        cell.traffic = _merge(cell.traffic, SERVE)
    return cell


def args(name: str, seed: int = 7, seconds: float = 1.0, trace: int = 0):
    return argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace)


def cpu_gate(chips: int):
    import jax
    return jax.devices()[:chips]


def entry(name: str):
    """The benchmark's entry script `name`.py, imported under its own name."""
    return spec.load_module(HERE / f"{name}.py", f"chipbench_{name}")
