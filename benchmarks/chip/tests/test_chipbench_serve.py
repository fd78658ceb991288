"""The serve cell at test size, under its own mix and under the unshared
control mix: a sound run is correct, the fp8 control is not, and a run
with the timed path broken underneath is not."""
import numpy as np
import pytest

import cells
from repro.models.registry import ModelApi
from repro.serve import ServeEngine

REAL_SERVE = ServeEngine.serve
REAL_DECODE = ModelApi.decode_step


CELL = "phi4mini-kv-reuse"
# the reuse cell's own traffic, and the unshared control mix
MIXES = ["phi4mini-reuse", "phi4mini-unique"]


def _run(mix, **traffic):
    cell = cells.small_cell(CELL, mix)
    cell.traffic.update(traffic)
    return cells.entry("run").run(cells.args(CELL, seed=2 ** 33 + 9,
                                             seconds=0.6),
                                  gate=cells.cpu_gate, cell=cell)


@pytest.mark.parametrize("mix", MIXES)
def test_sound_run_is_correct(mix):
    res = _run(mix)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "serve_p50_ms", "serve_p90_ms"}
    assert list(res)[-1] == "checks"


# At the test widths the fp8 control moves the greedy token only where a
# larger vocabulary and more layers leave near-ties; at this size the
# program reads 0 to 0.0203 and the control 0.2107 to 0.4533 (seeds 1-3),
# so the limit here lies between them and not at the full size's.
CONTROL_SIZE = {"vocab_size": 8192, "num_hidden_layers": 16}
CONTROL_LIMIT = 0.1


def test_fp8_control_fails_the_limit():
    control = cells.entry("control")
    cell = cells.small_cell(CELL)
    cell.config.update(CONTROL_SIZE, limits={"logit_gap": CONTROL_LIMIT})
    cell.traffic.update(new_tokens=16, check_requests=1000, rate_per_s=20.0)
    for row in control.readings(cell, [1, 2, 3], 0.6, gate=cells.cpu_gate):
        assert row["program"]["logit_gap"] <= CONTROL_LIMIT
        assert row["control"]["logit_gap"] > CONTROL_LIMIT


def _state_unchanged(self, params, token, caches, position):
    """A decode step that hands back what it was given: the same token
    again, the caches as they were."""
    import jax
    return 1e4 * jax.nn.one_hot(token, self.cfg.vocab_size), caches


def _half_the_batch(self, requests):
    k = max(1, len(requests) // 2)
    REAL_SERVE(self, requests[:k])
    for r in requests[k:]:
        r.output = np.zeros_like(requests[0].output)
    return requests


def _token_altered(self, requests):
    REAL_SERVE(self, requests)
    for r in requests:
        r.output[-1] = (r.output[-1] + 1) % self.model.cfg.vocab_size
    return requests


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("where,fault", [
    (ModelApi, ("decode_step", _state_unchanged)),
    (ServeEngine, ("serve", _half_the_batch)),
    (ServeEngine, ("serve", _token_altered)),
], ids=["state_unchanged", "half_the_batch", "token_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, mix, where, fault):
    monkeypatch.setattr(where, *fault)
    # full batches, and every finished request compared
    res = _run(mix, rate_per_s=100.0, check_requests=1000)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
