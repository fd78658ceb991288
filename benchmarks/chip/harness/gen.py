"""The benchmark's own traffic generators, read from the cell's files.

The replay grid's stream follows YCSB's core workload: reads of records
whose popularity is Zipfian by rank, the ranks scattered over the record
keys (YCSB scatters them by hashing, here by a permutation from the seed),
records of fieldcount x fieldlength bytes. The serving schedule is an
open-loop arrival process over a prompt pool. Every seed gets the same
amount of work: the same number of operations per answer, the same
multiset of inter-arrival gaps and of prompt popularity counts, drawn in
a seed-dependent order. Kept here so that later changes to the system
cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np
from scipy import stats

from .seeds import rng


# ---- records and replay segments (replay grid) ------------------------------

def zipf_cdf(n: int, alpha: float) -> np.ndarray:
    """Cumulative popularity of ranks 1..n under Zipf(alpha)."""
    p = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    return np.cumsum(p / p.sum())


def record_sizes(config: dict) -> np.ndarray:
    """Bytes of each record: fieldcount fields of fieldlength bytes."""
    if config["fieldlengthdistribution"] != "constant":
        raise ValueError("only constant field lengths are generated")
    return np.full(config["recordcount"],
                   float(config["fieldcount"] * config["fieldlength"]))


class Keys:
    """Which record each popularity rank is, for one seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        if traffic["requestdistribution"] != "zipfian":
            raise ValueError("only zipfian request distributions are generated")
        if traffic["readproportion"] != 1.0:
            raise ValueError("the replay grid replays reads only")
        n = config["recordcount"]
        self.cdf = zipf_cdf(n, traffic["zipfian_constant"])
        self.key_of_rank = rng(seed, "scramble").permutation(n).astype(np.int32)

    def segment(self, traffic: dict, seed: int, k: int) -> np.ndarray:
        """Record keys of the k-th replay segment (operationcount reads)."""
        u = rng(seed, f"segment/{k}").random(traffic["operationcount"])
        rank = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                          len(self.cdf) - 1)
        return self.key_of_rank[rank]


# ---- open-loop request schedule (serving) -----------------------------------

def arrival_gaps(traffic: dict, n: int, seed: int) -> np.ndarray:
    """n inter-arrival gaps (s): the n mid-quantiles of the configured law
    at the configured rate, shuffled by the seed."""
    a = traffic["arrivals"]
    q = (np.arange(n) + 0.5) / n
    mean = 1.0 / traffic["rate_per_s"]
    if a["process"] == "poisson":
        g = stats.expon.ppf(q, scale=mean)
    elif a["process"] == "gamma":      # cv > 1: bursts (BurstGPT-like)
        shape = 1.0 / a["cv"] ** 2
        g = stats.gamma.ppf(q, shape, scale=mean / shape)
    else:
        raise ValueError(f"unknown arrival process {a['process']!r}")
    return rng(seed, "arrivals").permutation(g)


def popularity_counts(n: int, pool: int, alpha: float) -> np.ndarray:
    """How often each of `pool` prompts is asked among n requests: Zipf
    shares rounded by largest remainder, so the counts sum to n."""
    p = np.diff(zipf_cdf(pool, alpha), prepend=0.0) * n
    c = np.floor(p).astype(np.int64)
    c[np.argsort(-(p - c))[: n - c.sum()]] += 1
    return c


def schedule(traffic: dict, seconds: float, vocab: int, seed: int):
    """(due times in s, prompt index per request, prompt table (P, S))."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    gaps = arrival_gaps(traffic, n, seed)
    due = np.cumsum(gaps) - gaps[0]
    share = traffic["sharing"]
    S = traffic["prompt_tokens"]
    if share["kind"] == "pool":
        counts = popularity_counts(n, share["pool"], share["zipf_alpha"])
        which = rng(seed, "popularity").permutation(
            np.repeat(np.arange(share["pool"]), counts))
        table = rng(seed, "prompt_pool").integers(
            0, vocab, (share["pool"], S), dtype=np.int32)
    elif share["kind"] == "unique":
        which = np.arange(n)
        table = rng(seed, "prompts").integers(0, vocab, (n, S), dtype=np.int32)
    else:
        raise ValueError(f"unknown sharing {share['kind']!r}")
    return due, which, table
