"""Shadow-policy panel: counterfactual dollars for every online policy.

Each `ShadowCache` is the live cache's own `ReplacementMachine`
(`repro.egress.replacement`) without bytes, plus the bill it would run up.
Run under the live cache's policy, a shadow evicts what the live cache
evicts and bills what it bills. The panel subscribes to the live cache's
`AccessEvent` stream and replays every request against all shadows
simultaneously, accruing the dollars each policy WOULD have billed —
without ever touching the `ObjectStore`, so shadowing bills $0 of extra
egress (asserted via per-consumer meters in tests).

Miss costs come from the event (`AccessEvent.miss_cost`, priced at access
time), so a mid-stream price flip (`ObjectStore.set_price`) is reflected
in every shadow's counterfactual bill exactly as in the live one.
"""
from __future__ import annotations

from repro.egress.cache import ONLINE_POLICIES, AccessEvent
from repro.egress.replacement import ReplacementMachine

__all__ = ["ShadowCache", "ShadowPanel"]


class ShadowCache(ReplacementMachine):
    """Metadata-only cache simulation: keys, sizes, priorities — no bytes."""

    def __init__(self, policy: str, capacity_bytes: float):
        super().__init__(policy, capacity_bytes)
        self.hits = 0
        self.misses = 0
        self.dollars = 0.0       # counterfactual: what this policy would bill

    def access(self, key: str, nbytes: int, miss_cost: float) -> bool:
        """Replay one request; returns True on a (counterfactual) hit."""
        self.request(key)
        if key in self:
            self.hits += 1
            self.touch(key, miss_cost)
            return True
        self.misses += 1
        self.dollars += miss_cost
        if self.fits(nbytes):
            self.insert(key, nbytes, miss_cost)
        return False

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class ShadowPanel:
    """One shadow cache per policy, all driven by the same event stream."""

    def __init__(self, capacity_bytes: float,
                 policies: tuple[str, ...] = ONLINE_POLICIES):
        self.shadows = {p: ShadowCache(p, capacity_bytes) for p in policies}

    def on_event(self, ev: AccessEvent) -> None:
        for sh in self.shadows.values():
            sh.access(ev.key, ev.nbytes, ev.miss_cost)

    @property
    def policies(self) -> tuple[str, ...]:
        return tuple(self.shadows)

    def dollars(self) -> dict[str, float]:
        return {p: sh.dollars for p, sh in self.shadows.items()}

    def snapshot(self) -> dict:
        return {p: dict(dollars=sh.dollars, hits=sh.hits, misses=sh.misses,
                        hit_rate=sh.hit_rate, used=sh.used)
                for p, sh in self.shadows.items()}
