"""The online replacement machine: LRU / LFU / GDS / GDSF in one place.

`ReplacementMachine` holds the resident set (each key's size and an
optional payload), the lazy-deletion heap keyed `(priority, touch, key)`,
per-key request counts, GreedyDual's inflation L and the access clock, and
it alone computes priorities and victims. `EgressCache` keeps its object
bytes here as payloads; a `ShadowCache` (`repro.online.shadow`) is this
machine with no payloads plus a counterfactual bill. A shadow running the
live cache's policy therefore evicts exactly what the live cache evicts,
which is what the governor's and the fleet's swap decisions rest on
(DESIGN.md §8).

The formulas are those of `core/policies.py`, the independent host oracle:
LRU = the clock, LFU = the request count, GDS = L + c/s and GDSF =
L + f*c/s, where c is the miss cost in dollars at the price in effect. The
smallest (priority, last touch, key) is evicted first, and under GDS/GDSF
an eviction sets L to the victim's priority.
"""
from __future__ import annotations

import heapq
from typing import Any, Callable

__all__ = ["ONLINE_POLICIES", "ReplacementMachine"]

ONLINE_POLICIES = ("lru", "lfu", "gds", "gdsf")


class ReplacementMachine:
    """Resident set + priorities of a byte-budgeted cache, without I/O."""

    def __init__(self, policy: str, capacity_bytes: float):
        assert policy in ONLINE_POLICIES, policy
        self.policy = policy
        self.capacity = float(capacity_bytes)
        self.used = 0.0
        self.clock = 0
        self.inflation = 0.0                # GreedyDual's L
        self.freq: dict[str, int] = {}
        # key -> (priority, touch, nbytes, payload); a heap entry
        # (priority, touch, key) is live iff it matches its key's entry
        self._resident: dict[str, tuple[float, int, int, Any]] = {}
        self._heap: list[tuple[float, int, str]] = []

    def __contains__(self, key: str) -> bool:
        return key in self._resident

    def payload(self, key: str) -> Any:
        return self._resident[key][3]

    def resident(self) -> dict[str, Any]:
        """key -> payload of every resident object, in insertion order."""
        return {k: e[3] for k, e in self._resident.items()}

    def request(self, key: str) -> int:
        """Advance the clock and count one request for `key`; returns the
        key's request count (the frequency admission is asked with)."""
        self.clock += 1
        freq = self.freq[key] = self.freq.get(key, 0) + 1
        return freq

    def fits(self, nbytes: int) -> bool:
        """False = the object is larger than the cache: fetch-through."""
        return nbytes <= self.capacity

    def _priority(self, key: str, nbytes: int, miss_cost: float) -> float:
        policy = self.policy
        if policy == "lru":
            return float(self.clock)
        if policy == "lfu":
            return float(self.freq[key])
        dens = miss_cost / max(nbytes, 1)
        if policy == "gds":
            return self.inflation + dens
        return self.inflation + self.freq[key] * dens  # gdsf

    def _place(self, key: str, nbytes: int, miss_cost: float, payload: Any,
               touch: int) -> None:
        pr = self._priority(key, nbytes, miss_cost)
        self._resident[key] = (pr, touch, nbytes, payload)
        heapq.heappush(self._heap, (pr, touch, key))

    def touch(self, key: str, miss_cost: float) -> None:
        """A hit on resident `key` at the current clock."""
        _, _, nbytes, payload = self._resident[key]
        self._place(key, nbytes, miss_cost, payload, self.clock)

    def insert(self, key: str, nbytes: int, miss_cost: float,
               payload: Any = None) -> list[tuple[str, int, Any]]:
        """Evict until `nbytes` fit, then admit `key`. Returns the victims
        as (key, nbytes, payload), in eviction order."""
        victims = []
        while self.used + nbytes > self.capacity and self._resident:
            pr, touch, victim = heapq.heappop(self._heap)
            entry = self._resident.get(victim)
            if entry is None or entry[1] != touch or entry[0] != pr:
                continue                    # stale heap entry
            del self._resident[victim]
            self.used -= entry[2]
            if self.policy in ("gds", "gdsf"):
                self.inflation = pr
            victims.append((victim, entry[2], entry[3]))
        self.used += nbytes
        self._place(key, nbytes, miss_cost, payload, self.clock)
        return victims

    def set_policy(self, policy: str,
                   miss_cost: Callable[[int], float]) -> None:
        """Switch policy in place: reset L, recompute every resident
        priority (miss costs from `miss_cost(nbytes)` at the price in
        effect), keep each last touch, rebuild the heap. Evicts nothing."""
        assert policy in ONLINE_POLICIES, policy
        self.policy = policy
        self.inflation = 0.0
        self._heap = []
        for key, (_, touch, nbytes, payload) in self._resident.items():
            self._place(key, nbytes, miss_cost(nbytes), payload, touch)
