"""Plain host reference of the uniform-page replay grid (configurations of
kind "replay_grid"): one straightforward loop per (policy, price vector,
page budget) cell, independent of the system under test.

Semantics (the paper's eq. (2) regime, one page per key):
- a request for a cached key is a hit; a miss pays the key's cost
  c = f + s * e and always inserts the key;
- a miss on a full cache first evicts the cached key with the least
  (score, last-touch) pair;
- scores: LRU last touch; LFU request count so far (every request counts,
  cached or not); GDS L + c/s and GDSF L + count * c/s, frozen at the
  key's last touch, where L is the score of the last victim; Belady minus
  the key's next request index; cost-Belady minus s * max(next - now, 1)
  / c; a key never requested again scores minus infinity under both.

Every score but cost-Belady's is frozen at the key's last touch, so those
cells keep the cached keys in a heap of (score, touch) entries and drop
stale entries as they surface; cost-Belady's score moves with `now`, so it
scans the cached keys at each eviction.

Scores are computed in the configuration's stated precision (float32) and
dollars are summed in float64, as billed. With `precision="bfloat16"` the
scores, the costs and the sum are all bfloat16: the control.
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
           precision: str = "float32") -> float:
    """Dollars one cell pays over the request sequence `ids`."""
    f = np.float32 if precision == "float32" else ml_dtypes.bfloat16
    acc = np.float64 if precision == "float32" else ml_dtypes.bfloat16
    T = len(ids)
    keys, local = np.unique(ids, return_inverse=True)
    c = costs[keys]
    cf = c.astype(np.float32).astype(f)                # score operands
    sf = sizes[keys].astype(np.float32).astype(f)
    cos = (cf / sf).astype(f)
    bill = c.astype(acc)
    nxt = next_use(ids)
    n = len(keys)
    cached = np.zeros(n, bool)
    stored = np.zeros(n, np.int64)
    touch = np.zeros(n, np.int64)
    freq = np.zeros(n, np.int64)
    heap = []                  # (frozen score, touch, key), stale included
    used, L, dollars = 0, f(0.0), acc(0.0)
    for t in range(T):
        i = local[t]
        freq[i] += 1
        if not cached[i]:
            dollars = acc(dollars + bill[i])
            if used >= budget:
                if policy == "cost_belady":
                    members = np.flatnonzero(cached)
                    gap = np.maximum(stored[members] - t, 1).astype(f)
                    sc = (-((sf[members] * gap).astype(f)
                            / cf[members])).astype(f)
                    sc = np.where(stored[members] >= T, f(_NEVER), sc)
                    tied = members[sc == sc.min()]
                    victim = tied[np.argmin(touch[tied])]
                else:
                    while True:
                        lo, when, victim = heapq.heappop(heap)
                        if cached[victim] and touch[victim] == when:
                            break
                    if policy in ("gds", "gdsf"):
                        L = f(lo)
                cached[victim] = False
                used -= 1
            cached[i] = True
            used += 1
        if policy == "lru":
            score = f(t)
        elif policy == "lfu":
            score = f(freq[i])
        elif policy == "gds":
            score = f(L + cos[i])
        elif policy == "gdsf":
            score = f(L + f(f(freq[i]) * cos[i]))
        elif policy == "belady":
            score = f(_NEVER) if nxt[t] >= T else f(-nxt[t])
        else:
            score = None
        stored[i] = nxt[t]
        touch[i] = t
        if score is not None:
            heapq.heappush(heap, (float(score), t, i))
    return float(dollars)


def grid(ids, cost_matrix, sizes, budgets, policies=POLICIES,
         precision: str = "float32") -> np.ndarray:
    """Dollars of every (policy, price vector, budget) cell."""
    out = np.zeros((len(policies), len(cost_matrix), len(budgets)))
    for q, pol in enumerate(policies):
        for p, costs in enumerate(cost_matrix):
            for k, b in enumerate(budgets):
                out[q, p, k] = replay(pol, ids, costs, sizes, int(b), precision)
    return out
