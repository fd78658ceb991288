"""The copied generators reproduce from a seed and give every seed the same
amount of work; rates and percentiles are over all the work of a window."""
import numpy as np
import pytest

import cells  # noqa: F401  (puts the harness on sys.path)
from harness import gen, stats
from harness.seeds import jax_key, rng

YCSB = {"recordcount": 1 << 14, "fieldcount": 10, "fieldlength": 100,
        "fieldlengthdistribution": "constant"}
READS = {"requestdistribution": "zipfian", "zipfian_constant": 0.99,
         "readproportion": 1.0, "operationcount": 512}
SERVE = {"rate_per_s": 2.0, "arrivals": {"process": "poisson"},
         "prompt_tokens": 16, "sharing": {"kind": "pool", "pool": 32,
                                          "zipf_alpha": 1.0}}
BIG = 2 ** 33 + 17


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_replay_segments_reproduce(seed):
    keys = gen.Keys(YCSB, READS, seed)
    a = keys.segment(READS, seed, 3)
    assert np.array_equal(a, gen.Keys(YCSB, READS, seed).segment(READS, seed, 3))
    assert not np.array_equal(a, keys.segment(READS, seed, 4))
    assert not np.array_equal(a, gen.Keys(YCSB, READS, seed + 1)
                              .segment(READS, seed + 1, 3))
    assert a.dtype == np.int32 and len(a) == 512
    assert a.min() >= 0 and a.max() < YCSB["recordcount"]


def test_zipfian_ranks_are_scattered_over_the_records():
    keys = gen.Keys(YCSB, READS, 3)
    assert sorted(keys.key_of_rank) == list(range(YCSB["recordcount"]))
    ids = np.concatenate([keys.segment(READS, 3, k) for k in range(40)])
    counts = np.bincount(ids, minlength=YCSB["recordcount"])
    # the hottest record is rank 1, wherever the seed put it
    assert counts.argmax() == keys.key_of_rank[0]
    share = counts.max() / len(ids)
    assert share == pytest.approx(1.0 / np.sum(np.arange(1, (1 << 14) + 1)
                                               ** -0.99), rel=0.1)


def test_records_are_fieldcount_by_fieldlength_bytes():
    s = gen.record_sizes(YCSB)
    assert s.shape == (1 << 14,) and np.all(s == 1000.0)
    with pytest.raises(ValueError):
        gen.record_sizes(dict(YCSB, fieldlengthdistribution="zipfian"))
    with pytest.raises(ValueError):
        gen.Keys(YCSB, dict(READS, readproportion=0.95), 1)


def test_schedule_same_work_for_every_seed():
    runs = [gen.schedule(SERVE, 50.0, 1000, seed) for seed in (1, 2, BIG)]
    for due, which, table in runs:
        assert len(due) == 100 and due[0] == 0.0
        assert table.shape == (32, 16)
    gaps = [np.sort(np.diff(r[0])) for r in runs]
    counts = [np.bincount(r[1], minlength=32) for r in runs]
    # the same multiset of popularity counts, in another order
    assert all(np.array_equal(counts[0], c) for c in counts)
    assert not np.array_equal(runs[0][1], runs[1][1])
    # the same multiset of gaps (up to the one left out of the diffs)
    full = [np.sort(gen.arrival_gaps(SERVE, 100, s)) for s in (1, 2)]
    assert np.array_equal(full[0], full[1])
    assert np.mean(full[0]) == pytest.approx(0.5, rel=0.05)
    assert len(gaps[0]) == 99


def test_unique_prompts_and_bursty_gaps():
    tr = dict(SERVE, sharing={"kind": "unique"},
              arrivals={"process": "gamma", "cv": 2.0})
    due, which, table = gen.schedule(tr, 20.0, 1000, 5)
    assert len(set(map(bytes, table))) == len(table) == len(due) == 40
    g = gen.arrival_gaps(tr, 400, 5)
    assert np.std(g) / np.mean(g) > 1.5


def test_seed_streams_are_independent_and_take_any_size():
    assert rng(BIG, "a").random() != rng(BIG, "b").random()
    assert rng(BIG, "a").random() == rng(BIG, "a").random()
    assert rng(BIG, "a").random() != rng(BIG + 2 ** 40, "a").random()
    k = jax_key(BIG, "weights")
    assert k.shape == ()


def test_rate_is_all_work_over_the_whole_window():
    # three answers of unequal length: the rate is total over total,
    # not the mean of per-answer rates
    work, secs = [100, 100, 100], [1.0, 1.0, 2.0]
    r = stats.rate(sum(work), sum(secs))
    assert r == 75.0
    assert r != np.mean([w / s for w, s in zip(work, secs)])
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_percentiles_are_over_all_requests_not_chunks():
    lat = np.concatenate([np.full(80, 1.0), np.full(20, 100.0)])
    chunks = np.split(lat, 10)
    assert stats.percentile(lat, 90) == 100.0
    assert np.median([np.percentile(c, 90) for c in chunks]) == 1.0
    assert stats.percentile(lat, 50) == 1.0
