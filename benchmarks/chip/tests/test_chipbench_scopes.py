"""The scan step's per-scope device time, read from the small trace recorded
on a TPU v5e (one 96-cell grid answer over 2^16 keys and 64 requests, with
the Mosaic victim kernel) through a scope map of its instruction names."""
import pathlib

import pytest

import cells
import repro.core.policies_jax as pj
from harness import profile, spec

TRACE = pathlib.Path(__file__).parent / "data" / "grid_small.xplane.pb"
STEPS = 64
# scope -> instructions, as the program maps those of this trace;
# bitcast.81 runs no device op and is not in the trace
SCOPES = {
    "replay.score": ["negate_select_fusion.5", "copy.33", "add_add_fusion.2",
                     "copy.43", "dynamic-update-slice.55"],
    "replay.victim": ["evict_argmin_pallas.7", "reshape.418", "reshape.419",
                      "convert.28", "bitcast.81"],
    "replay.update": ["fusion.35", "dynamic-update-slice.56",
                      "dynamic-update-slice.57", "fusion.34"],
    "unscoped": ["dynamic_slice.27", "dynamic_slice.28"],
}
# the device ns of each scope's ops in the trace, over its 64 steps
WANT_MS = {
    "grid.score_ms_per_step": (4760649 + 1590469 + 1026052 + 620392
                               + 170691) / STEPS / 1e6,
    "grid.victim_ms_per_step": (8319669 + 2661934 + 2772717 + 829105)
    / STEPS / 1e6,
    "grid.update_ms_per_step": (559889 + 130609 + 130187 + 110514)
    / STEPS / 1e6,
}


@pytest.fixture(scope="module")
def red():
    return profile.reduce_trace(TRACE)


def _run(red, steps=STEPS):
    return cells.entry("run").Run(cells.small_cell("ycsbc-grid"), red,
                                  {"scan_steps_traced": steps}, [], {}, {})


@pytest.mark.parametrize("metric", sorted(WANT_MS))
def test_scope_readers_on_recorded_trace(red, monkeypatch, metric):
    monkeypatch.setattr(pj, "step_scopes", lambda: SCOPES)
    got = spec.metric_reader(metric)(_run(red))
    assert got == pytest.approx(WANT_MS[metric], rel=1e-12)
    # the victim kernel lies inside the victim scope
    if metric == "grid.victim_ms_per_step":
        assert red.kernels["evict_argmin_pallas"]["ns"] / STEPS / 1e6 < got
    # the three scopes hold no more than the device's busy time
    total = sum(spec.metric_reader(m)(_run(red)) for m in WANT_MS)
    assert total < red.busy_ns / STEPS / 1e6


@pytest.mark.parametrize("metric", sorted(WANT_MS))
def test_scope_readers_silent_without_a_map(red, monkeypatch, metric):
    """A program without `step_scopes` (an older one), a map without the
    scope, or a run that traced no steps gives no number."""
    read = spec.metric_reader(metric)
    monkeypatch.delattr(pj, "step_scopes")
    assert read(_run(red)) is None
    monkeypatch.setattr(pj, "step_scopes", lambda: {}, raising=False)
    assert read(_run(red)) is None
    monkeypatch.setattr(pj, "step_scopes", lambda: SCOPES)
    assert read(_run(red, steps=0)) is None
