"""Batched serving engine with an egress-billed prefix cache.

The serving-side instantiation of the paper: decoded prefixes' KV blocks
are objects in cloud storage (billed per GET + per byte when re-fetched);
a local EgressCache with a dollar-aware policy decides which prefix KVs
stay resident. `audit()` measures the engine's realized dollar-regret
against the exact offline reference.

The engine itself is a straightforward continuous-batching loop over the
model's prefill/decode steps — adequate for the examples; the dry-run
exercises the production shapes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.egress.cache import EgressCache
from repro.egress.store import ObjectStore
from repro.fleet import Fleet
from repro.models.registry import ModelApi
from repro.obs import MetricsRegistry
from repro.online import DollarGovernor, WindowedAuditor

__all__ = ["ServeEngine", "Request"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 8
    output: Optional[np.ndarray] = None


def _prefix_key(tokens: np.ndarray) -> str:
    return "prefix/" + hashlib.sha1(tokens.tobytes()).hexdigest()[:16]


class ServeEngine:
    def __init__(self, model: ModelApi, params,
                 store: Optional[ObjectStore] = None,
                 prefix_cache_bytes: float = 1 << 24,
                 policy: str = "gdsf", govern: bool = False,
                 governor_window: int = 64, hysteresis: float = 0.05,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer=None, events=None, fleet_nodes: int = 0):
        self.model = model
        self.params = params
        self.store = store or ObjectStore("gcs_internet")
        self.metrics = metrics or MetricsRegistry()
        # observability (DESIGN.md §9): one tracer threads through engine ->
        # cache -> store so request/cache.get/store.get spans nest; the
        # decision event log rides on the cache
        self.tracer = tracer
        self.events = events
        if tracer is not None:
            self.store.set_tracer(tracer)
        # fleet mode (DESIGN.md §10): partition the prefix cache across
        # `fleet_nodes` hash-sharded hosts, each with its own billing meter
        # and shadow panel, governed by quorum swaps over gossip; the
        # single-host cache and governor are replaced wholesale
        assert not (govern and fleet_nodes), \
            "govern= and fleet_nodes= are mutually exclusive governors"
        self.fleet: Optional[Fleet] = None
        self.cache: Optional[EgressCache] = None
        if fleet_nodes:
            self.fleet = Fleet(
                store=self.store, n_nodes=fleet_nodes,
                capacity_bytes=prefix_cache_bytes / fleet_nodes,
                policy=policy, window_span=4.0 * governor_window,
                max_skew=float(governor_window),
                gossip_every=governor_window,
                events=events, metrics=self.metrics)
        else:
            self.cache = EgressCache(self.store, prefix_cache_bytes, policy,
                                     consumer="serve_prefix_cache",
                                     metrics=self.metrics, tracer=tracer,
                                     events=events)
        self.governor: Optional[DollarGovernor] = None
        if govern:
            auditor = WindowedAuditor(prefix_cache_bytes,
                                      window=4 * governor_window,
                                      metrics=self.metrics)
            self.governor = DollarGovernor(
                self.cache, window=governor_window, hysteresis=hysteresis,
                auditor=auditor, metrics=self.metrics)
        self._decode = jax.jit(
            lambda p, t, c, i: model.decode_step(p, t, c, i))

    # ------------------------------------------------------------------
    def _prefill_batch(self, prompts: np.ndarray):
        """Run prefill; persist each row's prefix KV to the object store so
        identical prefixes can be re-fetched (billed) or served from the
        local egress cache."""
        with self._span("serve.prefill", batch=prompts.shape[0]):
            logits, caches = self.model.prefill(
                self.params, {"tokens": jnp.asarray(prompts)})
            if self.tracer:     # the span ends when the outputs are ready
                jax.block_until_ready((logits, caches))
        with self._span("serve.kv_persist"):
            for b in range(prompts.shape[0]):
                key = _prefix_key(prompts[b])
                if not self.store.contains(key):
                    # store one row's KV bytes (serialized, billed on
                    # re-fetch)
                    row = [np.asarray(kv[0][b]) for kv in caches]
                    blob = b"".join(r.tobytes() for r in row)
                    self.store.put(key, blob)
        return logits, caches

    def _span(self, name: str, **attrs):
        """Engine-level span, or a nullcontext when tracing is off."""
        if not self.tracer:
            return contextlib.nullcontext()
        return self.tracer.span(name, cat="serve", **attrs)

    def serve(self, requests: list[Request]) -> list[Request]:
        """Batch requests of equal prompt length and decode greedily."""
        with self._span("serve.batch", requests=len(requests)):
            self._serve(requests)
        self.metrics.inc("serve.requests", len(requests))
        return requests

    def _serve(self, requests: list[Request]) -> None:
        by_len: dict[int, list[Request]] = {}
        for r in requests:
            by_len.setdefault(len(r.prompt), []).append(r)
        for _, group in sorted(by_len.items()):
            prompts = np.stack([r.prompt for r in group])
            # prefix-cache touch: hit = KV stays local, miss = billed fetch
            for r in group:
                key = _prefix_key(r.prompt)
                if self.store.contains(key):
                    with self._span("serve.request", rid=r.rid):
                        if self.fleet is not None:
                            self.fleet.access(key)
                        else:
                            self.cache.get(key)
            logits, caches = self._prefill_batch(prompts)
            S = prompts.shape[1]
            max_new = max(r.max_new_tokens for r in group)
            caches = _grow(self.model, caches, S + max_new)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            outs = [tok]
            # the span ends when the tokens are on the host
            with self._span("serve.decode", batch=len(group), steps=max_new):
                for step in range(max_new - 1):
                    logits, caches = self._decode(self.params, tok, caches,
                                                  jnp.int32(S + step))
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                    outs.append(tok)
                gen = np.stack([np.asarray(t) for t in outs], 1)
            for i, r in enumerate(group):
                r.output = gen[i][:r.max_new_tokens]

    def audit(self):
        """Exact offline audit: per-host dict in fleet mode (each host's
        own partition trace), single audit otherwise."""
        if self.fleet is not None:
            return self.fleet.audits()
        return self.cache.audit()

    def governance_snapshot(self) -> dict:
        """Metrics + governor + obs state, the JSON-exportable view."""
        snap = dict(metrics=self.metrics.snapshot(),
                    store=self.store.meter.snapshot(),
                    consumers=self.store.consumer_snapshot())
        if self.governor is not None:
            snap["governor"] = self.governor.snapshot()
        if self.fleet is not None:
            snap["fleet"] = self.fleet.snapshot()
        if self.events is not None:
            snap["events"] = self.events.snapshot()
        if self.tracer:
            snap["spans"] = self.tracer.to_dicts()
        return snap


def _grow(model: ModelApi, caches, max_len: int):
    cfg = model.cfg
    if cfg.family in ("dense", "moe", "vlm"):
        out = []
        for (k, v) in caches:
            pad = max_len - k.shape[1]
            if pad > 0:
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            out.append((k, v))
        return out
    if cfg.family == "encdec":
        out = []
        for (sk, sv, ck, cv) in caches:
            pad = max_len - sk.shape[1]
            if pad > 0:
                sk = jnp.pad(sk, ((0, 0), (0, pad), (0, 0), (0, 0)))
                sv = jnp.pad(sv, ((0, 0), (0, pad), (0, 0), (0, 0)))
            out.append((sk, sv, ck, cv))
        return out
    return caches