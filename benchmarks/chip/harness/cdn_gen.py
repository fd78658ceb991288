"""The CDN replay stream: heavy-tailed object sizes, a popular core of
small objects and one-hit wonders, with the mean and largest object size
and the one-hit tail of the source paper's Wikipedia CDN arm, drawn by
the law of `core/trace.py`'s `wiki_cdn_like` (the configuration's
`from_source` and `assumed` say which is which).

Every seed gets the same work, as harness/gen.py's generators do:
- the catalog's sizes are the mid-quantiles of the configured law, so
  every seed has the same multiset of sizes and the same catalog bytes;
- every answer requests the same multiset of (size, request count) pairs:
  core popularity rank r is the r-th smallest object, requested as often
  as Zipf's shares rounded by largest remainder say, and the one-hit
  requests go to objects spread evenly over the rest of the size ranks,
  the largest among them;
- the largest one-hit objects arrive at the same steps of every answer,
  spread evenly over it: a miss on one of them evicts hundreds to
  thousands of objects, and how full the cache is when it comes decides
  how many, so their order alone moved an answer's eviction work by 2.6%
  (standard deviation over seeds) where these fixed steps leave 0.26%;
- the seed draws which key holds which size, and the order of every
  other request of each answer.
Kept here so that later changes to the system cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np

from .gen import popularity_counts
from .seeds import rng


def _law_quantiles(law: dict, n: int) -> np.ndarray:
    """The n mid-quantiles, ascending, of the Pareto body: scale x
    (1 - q)^(-1/shape), numpy's `pareto(shape) + 1` scaled, clipped at
    the largest size (the body starts at the scale)."""
    q = (np.arange(n) + 0.5) / n
    x = law["scale_bytes"] * (1.0 - q) ** (-1.0 / law["pareto_shape"])
    return np.minimum(x, law["max_bytes"])


def _core(config: dict, traffic: dict) -> int:
    return round(traffic["core_share_of_objects"] * config["objectcount"])


def one_hit_ranks(config: dict, traffic: dict) -> np.ndarray:
    """Size ranks of an answer's one-hit objects, largest first: spread
    evenly over the ranks outside the core, the largest among them."""
    n, n_core = config["objectcount"], _core(config, traffic)
    n_once = round(traffic["one_hit_share_of_requests"]
                   * traffic["operationcount"])
    if n_once > n - n_core:
        raise ValueError("more one-hit requests than objects outside the core")
    return n - 1 - (np.arange(n_once) * (n - n_core)) // n_once


def request_counts(config: dict, traffic: dict) -> np.ndarray:
    """Requests of each size rank (ascending sizes) in one answer."""
    n_core = _core(config, traffic)
    once = one_hit_ranks(config, traffic)
    counts = np.zeros(config["objectcount"], np.int64)
    counts[:n_core] = popularity_counts(traffic["operationcount"] - len(once),
                                        n_core, traffic["zipf_alpha"])
    counts[once] += 1
    return counts


def sizes_by_rank(config: dict, traffic: dict) -> np.ndarray:
    """Whole bytes of each size rank: the law's quantiles times the one
    factor (found by bisection) that gives the configured access-weighted
    mean over an answer's requests, clipped at the largest size."""
    law = config["size_law"]
    base = _law_quantiles(law, config["objectcount"])
    w = request_counts(config, traffic) / traffic["operationcount"]

    def sized(f):
        return np.minimum(np.round(f * base), law["max_bytes"])

    lo, hi = 1e-6, 1e6
    for _ in range(200):
        mid = np.sqrt(lo * hi)
        if w @ sized(mid) < law["access_weighted_mean_bytes"]:
            lo = mid
        else:
            hi = mid
    return sized(hi)


def fixed_steps(traffic: dict) -> np.ndarray:
    """Steps of the largest one-hit objects, largest first: evenly spaced
    over the answer, taken in bit-reversed order so that the largest
    objects fall apart from each other and over the whole answer."""
    n, T = traffic["largest_one_hit_on_fixed_steps"], traffic["operationcount"]
    bits = n.bit_length() - 1
    if n != 1 << bits or n > T:
        raise ValueError("the fixed steps number a power of two, at most "
                         "operationcount")
    slot = [int(format(j, f"0{bits}b")[::-1], 2) if bits else 0
            for j in range(n)]
    return np.round((np.asarray(slot) + 0.5) * T / n).astype(np.int64)


class Stream:
    """One seed's catalog (`sizes` per key, `catalog_bytes`) and its
    answers (`segment`)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        if traffic["readproportion"] != 1.0:
            raise ValueError("the replay grid replays reads only")
        ranked = sizes_by_rank(config, traffic)
        n = len(ranked)
        self.key_of_rank = rng(seed, "scramble").permutation(n).astype(
            np.int32)
        self.sizes = np.empty(n)
        self.sizes[self.key_of_rank] = ranked
        self.catalog_bytes = float(ranked.sum())
        counts = request_counts(config, traffic)
        self.steps = fixed_steps(traffic)
        largest = one_hit_ranks(config, traffic)[:len(self.steps)]
        if len(largest) < len(self.steps):
            raise ValueError("more fixed steps than one-hit requests")
        self.largest = self.key_of_rank[largest]
        counts[largest] = 0
        self.rest = np.repeat(self.key_of_rank, counts)

    def segment(self, traffic: dict, seed: int, k: int) -> np.ndarray:
        """Keys of the k-th answer's operationcount requests."""
        out = np.empty(traffic["operationcount"], np.int32)
        free = np.ones(len(out), bool)
        free[self.steps] = False
        out[self.steps] = self.largest
        out[free] = rng(seed, f"segment/{k}").permutation(self.rest)
        return out
