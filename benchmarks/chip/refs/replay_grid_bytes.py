"""Plain host reference of the byte-budget replay grid (configurations of
kind "replay_grid_bytes"): one straightforward loop per (policy, price
vector, byte budget) cell, independent of the system under test.

Semantics (variable object sizes, a cache of B bytes):
- a request for a cached key is a hit; a miss pays the key's cost
  c = f + s * e;
- a miss on a key larger than B is fetched through: it is billed, nothing
  is evicted and the key is not inserted;
- any other miss evicts cached keys in (score, last-touch) order until
  the key's s bytes fit beside the rest (used + s <= B), then inserts it;
- scores: LRU last touch; LFU request count so far (every request counts,
  cached, fetched through or not); GDS L + c/s and GDSF L + count * c/s,
  frozen at the key's last touch, where L is the score of the last
  victim; Belady minus the key's next request index; cost-Belady minus
  s * max(next - now, 1) / c; a key never requested again scores minus
  infinity under both.

Every score but cost-Belady's is frozen at the key's last touch, so those
cells keep the cached keys in a heap of (score, touch) entries and drop
stale entries as they surface; cost-Belady's score moves with `now` but
not within a step, so each miss that must evict scores the cached keys
once and evicts the least in turn.

This is `core/policies.py`'s priority and oracle simulators, written
apart; it departs from them in nothing but precision. Sizes, used bytes
and budgets are whole bytes. Scores are computed in the configuration's
stated precision (float32) and dollars are summed in float64, as billed.
With `precision="bfloat16"` the scores, the costs and the sum are all
bfloat16: the control.
"""
from __future__ import annotations

import heapq

import ml_dtypes
import numpy as np

POLICIES = ("lru", "lfu", "gds", "gdsf", "belady", "cost_belady")
_NEVER = -3.4e38


def next_use(ids: np.ndarray) -> np.ndarray:
    """Index of each request's next request of the same key (T if none)."""
    T = len(ids)
    nxt = np.full(T, T, np.int64)
    last = {}
    for t in range(T - 1, -1, -1):
        nxt[t] = last.get(int(ids[t]), T)
        last[int(ids[t])] = t
    return nxt


def replay(policy: str, ids, costs, sizes, budget: int,
           precision: str = "float32") -> tuple[float, int, int]:
    """(dollars, evictions, fetch-throughs) of one cell over `ids`."""
    f = np.float32 if precision == "float32" else ml_dtypes.bfloat16
    acc = np.float64 if precision == "float32" else ml_dtypes.bfloat16
    T = len(ids)
    keys, local = np.unique(ids, return_inverse=True)
    c = costs[keys]
    cf = c.astype(np.float32).astype(f)                # score operands
    sf = sizes[keys].astype(np.float32).astype(f)
    cos = list((cf / sf).astype(f))
    bill = list(c.astype(acc))
    size = sizes[keys].astype(np.int64)
    sizes_ = size.tolist()
    nxt = next_use(ids).tolist()
    n = len(keys)
    cached = np.zeros(n, bool)   # an array, for cost-Belady's scan
    stored = np.zeros(n, np.int64)
    touch = np.zeros(n, np.int64)
    in_cache = [False] * n
    when = [0] * n
    freq = [0] * n
    heap = []                  # (frozen score, touch, key), stale included
    used, L, dollars = 0, f(0.0), acc(0.0)
    evictions = bypasses = 0
    for t, i in enumerate(local.tolist()):
        freq[i] += 1
        s = sizes_[i]
        if not in_cache[i]:
            dollars = acc(dollars + bill[i])
            if s > budget:
                bypasses += 1
                continue
            if used + s > budget and policy == "cost_belady":
                members = np.flatnonzero(cached)
                gap = np.maximum(stored[members] - t, 1).astype(f)
                sc = (-((sf[members] * gap).astype(f)
                        / cf[members])).astype(f)
                sc = np.where(stored[members] >= T, f(_NEVER),
                              sc).astype(np.float64)
                while used + s > budget:
                    tied = np.flatnonzero(sc == sc.min())
                    j = tied[np.argmin(touch[members[tied]])]
                    victim = int(members[j])
                    cached[victim] = in_cache[victim] = False
                    used -= sizes_[victim]
                    evictions += 1
                    sc[j] = np.inf
            while used + s > budget:
                lo, at, victim = heapq.heappop(heap)
                if not (in_cache[victim] and when[victim] == at):
                    continue
                if policy in ("gds", "gdsf"):
                    L = f(lo)
                cached[victim] = in_cache[victim] = False
                used -= sizes_[victim]
                evictions += 1
            cached[i] = in_cache[i] = True
            used += s
        when[i] = t
        if policy == "cost_belady":
            stored[i] = nxt[t]
            touch[i] = t
            continue
        if policy == "lru":
            score = f(t)
        elif policy == "lfu":
            score = f(freq[i])
        elif policy == "gds":
            score = f(L + cos[i])
        elif policy == "gdsf":
            score = f(L + f(f(freq[i]) * cos[i]))
        else:
            score = f(_NEVER) if nxt[t] >= T else f(-nxt[t])
        heapq.heappush(heap, (float(score), t, i))
    return float(dollars), evictions, bypasses


def grid(ids, cost_matrix, sizes, budgets, policies=POLICIES,
         precision: str = "float32", counts: bool = False):
    """Dollars of every (policy, price vector, budget) cell; with `counts`
    also their evictions and fetch-throughs, each of shape (Q, P, K)."""
    out = np.zeros((3, len(policies), len(cost_matrix), len(budgets)))
    for q, pol in enumerate(policies):
        for p, costs in enumerate(cost_matrix):
            for k, b in enumerate(budgets):
                out[:, q, p, k] = replay(pol, ids, costs, sizes, int(b),
                                         precision)
    return (out[0], out[1].astype(np.int64), out[2].astype(np.int64)) \
        if counts else out[0]
