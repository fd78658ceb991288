"""Request traces: container + synthetic generators.

A trace is (ids, sizes): `ids[t]` is the object requested at step t;
`sizes[i]` the byte size of object i. The container is offline, so the
paper's real arms (Twitter twemcache cluster-52, Wikipedia CDN) are
represented by statistics-matched synthetic stand-ins (see DESIGN.md §7):

- `twemcache_like`: Zipf(alpha~1.0) popularity over small objects,
  log-normal sizes with mean ~243 B (paper Table 1 trace stats).
- `wiki_cdn_like`: heavy-tailed sizes (mean ~37 KB, max ~94 MB), a
  one-hit-wonder tail covering about half the objects (paper Fig. 4 notes).
- `zipf_trace`: the paper's synthetic arm — Zipf popularity assigned
  independently of size, so cheap-hot vs expensive-cold tension exists.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Trace", "zipf_trace", "twemcache_like", "wiki_cdn_like", "two_class_trace"]


@dataclasses.dataclass(frozen=True)
class Trace:
    """A request stream over a fixed object universe."""

    ids: np.ndarray    # (T,) int32 — object requested at each step
    sizes: np.ndarray  # (N,) float64 — object sizes in bytes
    name: str = "trace"

    @property
    def num_requests(self) -> int:
        return int(self.ids.shape[0])

    @property
    def num_objects(self) -> int:
        return int(self.sizes.shape[0])

    def access_sizes(self) -> np.ndarray:
        return self.sizes[self.ids]

    def reuse_fraction(self) -> float:
        """Fraction of requests that are re-accesses (upper bound on any hit rate)."""
        first = np.zeros(self.num_objects, bool)
        reuse = 0
        for i in self.ids:
            if first[i]:
                reuse += 1
            first[i] = True
        return reuse / max(1, self.num_requests)


def _zipf_ids(rng: np.random.Generator, n_objects: int, n_requests: int,
              alpha: float) -> np.ndarray:
    ranks = np.arange(1, n_objects + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    p /= p.sum()
    return rng.choice(n_objects, size=n_requests, p=p).astype(np.int32)


def zipf_trace(n_objects: int = 500, n_requests: int = 5000, alpha: float = 0.9,
               size_dist: str = "lognormal", mean_size: float = 64 * 1024,
               sigma: float = 2.0, seed: int = 0, name: str = "zipf") -> Trace:
    """Paper's synthetic arm: Zipf popularity independent of size."""
    rng = np.random.default_rng(seed)
    ids = _zipf_ids(rng, n_objects, n_requests, alpha)
    if size_dist == "lognormal":
        # lognormal with the requested mean: mean = exp(mu + sigma^2/2)
        mu = np.log(mean_size) - sigma ** 2 / 2
        sizes = rng.lognormal(mu, sigma, size=n_objects)
    elif size_dist == "uniform":
        sizes = rng.uniform(1.0, 2 * mean_size, size=n_objects)
    else:
        raise ValueError(f"unknown size_dist {size_dist!r}")
    # shuffle sizes so popularity rank is independent of size
    rng.shuffle(sizes)
    return Trace(ids=ids, sizes=np.maximum(sizes, 1.0), name=name)


def two_class_trace(n_cheap: int = 50, n_exp: int = 20, n_requests: int = 4000,
                    cheap_size: float = 1024.0, exp_size: float = 1 << 30,
                    hot_fraction: float = 0.8, seed: int = 0) -> Trace:
    """Cheap-hot vs expensive-cold two-class workload (paper §1 example,
    used by the contention-frontier experiment §4/Fig. 2)."""
    rng = np.random.default_rng(seed)
    n = n_cheap + n_exp
    p = np.concatenate([
        np.full(n_cheap, hot_fraction / n_cheap),
        np.full(n_exp, (1 - hot_fraction) / n_exp),
    ])
    ids = rng.choice(n, size=n_requests, p=p).astype(np.int32)
    sizes = np.concatenate([np.full(n_cheap, cheap_size), np.full(n_exp, exp_size)])
    return Trace(ids=ids, sizes=sizes, name="two_class")


def twemcache_like(n_objects: int = 2000, n_requests: int = 20000,
                   seed: int = 0) -> Trace:
    """Twitter twemcache cluster-52 stand-in: small objects, mean ~243 B
    (narrow lognormal — memcache values cluster tightly in size)."""
    rng = np.random.default_rng(seed)
    ids = _zipf_ids(rng, n_objects, n_requests, alpha=1.0)
    sizes = rng.lognormal(np.log(200.0), 0.8, size=n_objects)
    sizes = np.clip(sizes, 16.0, 16 * 1024.0)
    sizes *= 243.0 / sizes[ids].mean()  # match *access-weighted* mean like the paper
    return Trace(ids=ids, sizes=np.maximum(sizes, 1.0), name="twemcache_like")


def wiki_cdn_like(n_objects: int = 6000, n_requests: int = 20000,
                  seed: int = 0) -> Trace:
    """Wikipedia CDN stand-in: mean ~37 KB, max ~94 MB, one-hit-wonder tail.

    Calibrated (pareto a=1.0, 55% one-hit tail) to land the paper's H=12-18
    band under egress-dominated pricing with low reuse — the largest
    objects are disproportionately single-touch (paper Fig. 4 caveats).
    """
    rng = np.random.default_rng(seed)
    # heavy-tail sizes: pareto body + a few huge objects
    sizes = (rng.pareto(1.0, size=n_objects) + 1.0) * 2048.0
    sizes = np.clip(sizes, 256.0, 94e6)
    order = np.argsort(sizes)  # sizes[order] ascending
    # popular core = smaller objects; one-hit tail = the rest (biggest last)
    n_core = int(n_objects * 0.45)
    core_ids = order[:n_core]
    tail_ids = order[n_core:]
    n_tail_req = min(len(tail_ids), n_requests // 3)
    core_req = _zipf_ids(rng, n_core, n_requests - n_tail_req, alpha=0.85)
    parts = [core_ids[core_req].astype(np.int32)]
    # each sampled tail object appears exactly once -> one-hit wonders
    parts.append(rng.choice(tail_ids, size=n_tail_req, replace=False).astype(np.int32))
    ids = np.concatenate(parts)
    rng.shuffle(ids)
    sizes = sizes * (37e3 / sizes[ids].mean())
    sizes = np.clip(sizes, 64.0, 94e6)
    return Trace(ids=ids, sizes=np.maximum(sizes, 1.0), name="wiki_cdn_like")


def next_use_indices(ids: np.ndarray, n_objects: int | None = None) -> np.ndarray:
    """next(t): index of the next request of the same object, or T if none.

    Vectorized: a stable sort groups each object's accesses in time order,
    so the successor within a group IS the next use.
    """
    ids = np.asarray(ids)
    T = ids.shape[0]
    if T == 0:
        return np.zeros(0, dtype=np.int64)
    order = np.argsort(ids, kind="stable")      # time order within each id
    sorted_ids = ids[order]
    succ = np.full(T, T, dtype=np.int64)
    same = sorted_ids[1:] == sorted_ids[:-1]
    succ[:-1][same] = order[1:][same]
    nxt = np.empty(T, dtype=np.int64)
    nxt[order] = succ
    return nxt
