"""The replay-grid cell at test size: a sound run is correct, the bfloat16
control is not, and a run with the timed path broken underneath is not."""
import numpy as np
import pytest

import cells
import repro.core.policies_jax as pj

CELL = "ycsbc-grid"
REAL = pj.sweep_jax


def _run(seed=2 ** 33 + 3):
    return cells.entry("run").run(cells.args(CELL, seed=seed, seconds=0.5),
                   gate=cells.cpu_gate, cell=cells.small_cell(CELL))


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "grid_cellreq_per_s"}
    assert set(res["checks"]) == {"dollar_rel_err", "dollar_rel_err.gdsf",
                                  "window_compiles"}
    assert list(res)[-1] == "checks"


def test_bfloat16_control_fails_the_limit():
    control = cells.entry("control")
    cell = cells.small_cell(CELL)
    limits = cell.config["limits"]
    for row in control.readings(cell, [1, 2, 3], 0.2, gate=cells.cpu_gate):
        assert set(row["control"]) == set(limits)
        assert all(row["program"][n] <= limits[n] for n in limits)
        assert any(row["control"][n] > limits[n] for n in limits)


def _state_unchanged(*a, **k):
    return np.zeros_like(REAL(*a, **k))


def _half_the_requests(policies, ids, *a, **k):
    return REAL(policies, ids[: len(ids) // 2], *a, **k)


def _answer_altered(*a, **k):
    out = np.array(REAL(*a, **k))
    out.flat[5] *= 1.01
    return out


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_requests,
                                   _answer_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(pj, "sweep_jax", fault)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["dollar_rel_err"]["value"] > \
        res["checks"]["dollar_rel_err"]["limit"]
