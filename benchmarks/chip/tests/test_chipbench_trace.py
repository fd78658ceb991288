"""The reduction from a profiler trace to busy/idle, kernel and op time,
checked on a small trace recorded on a TPU v5e (one 96-cell grid answer
over 2^16 keys and 64 requests, with the Mosaic victim kernel)."""
import pathlib

import pytest

import cells  # noqa: F401  (puts the harness on sys.path)
from harness import profile

TRACE = pathlib.Path(__file__).parent / "data" / "grid_small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return profile.reduce_trace(TRACE)


def test_window_busy_and_idle(red):
    assert red.chips == 1
    # the harness's bench.window span bounds the traced window
    assert red.window_ns == pytest.approx(29_903_800.0)
    assert 0 < red.busy_ns < red.window_ns
    assert 0.0 < red.idle_share < 1.0
    # every idle nanosecond over the gap threshold is charged to a span
    charged = sum(red.idle_by_span.values())
    assert charged <= red.window_ns - red.busy_ns + 1


def test_kernel_time_and_bytes_by_stable_name(red):
    k = red.kernels["evict_argmin_pallas"]
    assert k["runs"] == 64                       # one call per scan step
    per_call = 96 * 512 * 128 * 8 + 512 * 128 * 4 + 2 * 96 * 128 * 4
    assert k["bytes"] == 64 * per_call
    assert 0 < k["ns"] < red.busy_ns
    assert set(red.kernels) == {"evict_argmin_pallas"}


def test_programs_and_leaf_ops(red):
    assert red.modules["jit__sweep_grid"][0] == 1
    # the scan's while loop is a container: its time is not a leaf op's
    assert not any(v[2] == "while" for v in red.ops.values())
    top = red.device_ops(3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    assert top[0][0].startswith("evict_argmin_pallas")


def test_union_and_charge_on_intervals():
    covered, gaps = profile._union([(0, 4), (2, 6), (8, 9), (20, 30)], 1, 25)
    assert covered == 5 + 1 + 5
    assert gaps == [(6, 8), (9, 20)]
    spans = [("bench.wait", 5, 12), ("bench.serve", 12, 40)]
    assert profile._charge((9, 20), spans) == "bench.serve"
    assert profile._charge((6, 8), spans) == "bench.wait"
    assert profile._charge((50, 60), spans) == "none"
