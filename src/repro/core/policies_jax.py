"""JAX (lax.scan) cache-policy simulator — the TPU-native replay engine.

The paper's sweep experiments replay the same trace under hundreds of
(policy, price-vector, budget) cells. Sequential heap-based simulation does
not vectorize; here each policy step is a pure function over fixed-size
state arrays and the whole replay is one `lax.scan`, vmap-able across cells
and jit-able onto accelerators.

Policies are encoded as *score weights*: the victim is the cached object
with the minimum score, where

  score(i) = w_t * last_touch(i)                     (LRU)
           + w_f * freq(i)                           (LFU)
           + w_gd   * (L + c_i / s_i)                (GreedyDual-Size)
           + w_gdsf * (L + freq(i) * c_i / s_i)      (GDSF)
           + w_bel  * (-next_use(i))                 (Belady: evict farthest)
           + w_cb   * (-(s_i * gap_i / c_i))         (cost-aware Belady)

Because policies are just weight vectors, a whole policy *panel* batches as
one more vmap axis: `sweep_jax` compiles a single (policies x price-vectors
x budgets) grid program, the device-resident form of the paper's regime
maps (DESIGN.md §3).

Victim selection dispatches through `kernels.evict_argmin`: the Pallas TPU
kernel on TPU backends (`use_pallas=None` -> `on_tpu()`), the pure-jnp
reduction elsewhere; both implement the same lexicographic argmin and are
checked step-for-step against each other in tests/test_policies_jax.py.

Each scan step runs in three `jax.named_scope`s (`STEP_SCOPES`): the score
pass, victim selection and the state update. `step_scopes()` maps the
compiled grid's instructions to them, so a profiler trace's device time
splits by phase of the step; `sweep_jax(tracer=...)` records the host
phases of each call as spans (DESIGN.md §9).

Uniform-size mode (the paper's exact-reference regime): one eviction per
miss, no data-dependent loop. Variable sizes stay on the host reference
(`policies.py`); see DESIGN.md §3.

Validated step-for-step against `policies.py` in tests/test_policies_jax.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .trace import next_use_indices
from ..kernels import ops
from ..kernels.layout import LANES, to_tiles

__all__ = ["PolicyWeights", "POLICY_WEIGHTS", "STEP_SCOPES", "simulate_jax",
           "sweep_jax", "stack_policy_weights", "step_scopes"]

_BIG = jnp.float32(3.4e38)
# the named scopes that partition the scan step: the touch bookkeeping and
# the score pass, victim selection, and the state update
STEP_SCOPES = ("replay.score", "replay.victim", "replay.update")
# grid programs compiled ahead of their first call, by (argument shapes,
# num_objects, use_pallas); the oldest goes when a new shape would pass
# the bound
_EXECUTABLES: dict = {}
_MAX_EXECUTABLES = 8


@dataclasses.dataclass(frozen=True)
class PolicyWeights:
    w_t: float = 0.0
    w_f: float = 0.0
    w_gd: float = 0.0
    w_gdsf: float = 0.0
    w_bel: float = 0.0
    w_cb: float = 0.0

    def as_array(self) -> np.ndarray:
        return np.array([self.w_t, self.w_f, self.w_gd, self.w_gdsf,
                         self.w_bel, self.w_cb], dtype=np.float32)


POLICY_WEIGHTS: dict[str, PolicyWeights] = {
    "lru": PolicyWeights(w_t=1.0),
    "lfu": PolicyWeights(w_f=1.0, w_t=1e-12),
    "gds": PolicyWeights(w_gd=1.0),
    "gdsf": PolicyWeights(w_gdsf=1.0),
    "belady": PolicyWeights(w_bel=1.0),
    "cost_belady": PolicyWeights(w_cb=1.0),
}


def stack_policy_weights(policies: Sequence[str | PolicyWeights]) -> np.ndarray:
    """(Q, 6) weight stack for a policy panel — the third sweep axis."""
    rows = []
    for p in policies:
        w = POLICY_WEIGHTS[p] if isinstance(p, str) else p
        rows.append(w.as_array())
    return np.stack(rows)


def _static_score(w, t, freq_i, infl, c_over_s):
    """Frozen-at-touch score components (LRU / LFU / GDS / GDSF)."""
    return (w[0] * t + w[1] * freq_i
            + w[2] * (infl + c_over_s)
            + w[3] * (infl + freq_i * c_over_s))


@functools.partial(jax.jit,
                   static_argnames=("num_objects", "use_pallas", "trace_steps"))
def _simulate(ids, nxt, costs, sizes, capacity, weights, num_objects: int,
              use_pallas: bool = False, trace_steps: bool = False):
    """One policy replay, uniform-size pages. Returns (dollars, hits).

    Victim = lexicographic argmin of (score, last_touch) over cached objects,
    where score = static (frozen at touch) + dynamic (Belady / cost-Belady,
    evaluated at eviction time from the stored next-use index). This exactly
    matches the heap key of the Python reference.

    The per-object state is kept as (rows, 128) tiles, object i at
    (i // 128, i % 128), laid out once before the scan as the victim kernel
    reads it; `cached` holds int32 0/1 flags, the kernel's mask, and the
    padding past `num_objects` is never cached. Costs and sizes are tiled
    for the whole-table score; the bill and the frozen score read one
    entry a step from the flat costs and c/s.

    `use_pallas` routes the victim argmin through the Pallas TPU kernel
    (`kernels.evict_argmin`) instead of the jnp reduction — the replay
    engine's eviction hot path on real TPUs. `trace_steps` additionally
    returns the per-step (dollars, hits) trajectory for step-for-step
    equivalence tests.
    """
    T = ids.shape[0]
    if costs.shape != (num_objects,) or sizes.shape != (num_objects,):
        raise ValueError(f"costs {costs.shape} and sizes {sizes.shape} must "
                         f"have one entry per object ({num_objects})")
    c_over_s = (costs / jnp.maximum(sizes, 1e-30)).astype(jnp.float32)
    cost_tiles, _ = to_tiles(costs, ops.EVICT_BLOCK_N, 0.0)
    size_tiles, _ = to_tiles(sizes, ops.EVICT_BLOCK_N, 1.0)
    tiles = cost_tiles.shape
    INT_BIG = jnp.int32(2**31 - 1)

    def total_scores(static, stored_nxt, t):
        """static + dynamic part, per object."""
        nxtf = stored_nxt.astype(jnp.float32)
        gap = jnp.maximum(nxtf - t, 1.0)
        never = stored_nxt >= T
        # belady: evict max next-use  -> score -nxt (never-reused = -BIG)
        bel = jnp.where(never, -_BIG, -nxtf)
        # cost-belady: evict max s*gap/c -> score -(s*gap/c)
        cb = jnp.where(never, -_BIG,
                       -(size_tiles * gap / jnp.maximum(cost_tiles, 1e-30)))
        return static + weights[4] * bel + weights[5] * cb

    def step(state, inp):
        cached, static, stored_nxt, touch, freq, used, infl, dollars, hits = state
        t, i, nu = inp
        with jax.named_scope("replay.score"):
            tf = t.astype(jnp.float32)
            at = (i // LANES, i % LANES)
            freq = freq.at[at].add(1)
            # the flag is read as a value of its own: fused into the bill's
            # per-cell update, the slice made XLA relay the whole table
            is_hit = jax.lax.optimization_barrier(cached[at]) != 0
            dollars = dollars + jnp.where(is_hit, 0.0, costs[i])
            hits = hits + is_hit.astype(jnp.int32)
            raw = total_scores(static, stored_nxt, tf)
        with jax.named_scope("replay.victim"):
            # victim: lexicographic argmin of (score, last_touch, index)
            # among cached objects. The mask is `cached` itself, which
            # equals cached\{i}: a hit evicts nothing, so its victim goes
            # unused, and a miss's i is not cached.
            if use_pallas:
                victim, victim_score = ops.evict_argmin(raw, touch, cached,
                                                        use_pallas=True)
            else:
                scores = jnp.where(cached != 0, raw, _BIG)
                victim_score = jnp.min(scores)
                tie = scores <= victim_score  # exact; _BIG rows excluded
                t_tie = jnp.where(tie, touch, INT_BIG)
                index = (jax.lax.broadcasted_iota(jnp.int32, tiles, 0) * LANES
                         + jax.lax.broadcasted_iota(jnp.int32, tiles, 1))
                victim = jnp.min(jnp.where(tie & (t_tie == jnp.min(t_tie)),
                                           index, INT_BIG))
        with jax.named_scope("replay.update"):
            full = used >= capacity
            # eq.-(2) semantics: a miss always inserts (mandatory
            # displacement)
            do_insert = ~is_hit
            do_evict = do_insert & full & (victim_score < _BIG)
            out = (victim // LANES, victim % LANES)
            # an evicted victim is cached: clearing it is taking its 1 away
            cached = cached.at[out].add(-do_evict.astype(jnp.int32))
            # GreedyDual aging: L := priority of the evicted victim
            gd_active = (weights[2] + weights[3]) > 0
            infl = jnp.where(do_evict & gd_active, victim_score, infl)
            my_static = _static_score(weights, tf, freq[at].astype(jnp.float32),
                                      infl, c_over_s[i])
            used = used - jnp.where(do_evict, 1, 0) + jnp.where(do_insert, 1, 0)
            # a hit leaves i cached and a miss inserts it (never as the
            # victim, which is cached on a miss)
            cached = cached.at[at].set(1)
            # touches (hit or insert) refresh score, next-use and touch time
            static = static.at[at].set(my_static)
            stored_nxt = stored_nxt.at[at].set(nu)
            touch = touch.at[at].set(t)
        new_state = (cached, static, stored_nxt, touch, freq, used, infl,
                     dollars, hits)
        return new_state, ((dollars, hits) if trace_steps else None)

    init = (jnp.zeros(tiles, jnp.int32), jnp.full(tiles, _BIG, jnp.float32),
            jnp.full(tiles, T, jnp.int32), jnp.zeros(tiles, jnp.int32),
            jnp.zeros(tiles, jnp.int32), jnp.int32(0), jnp.float32(0.0),
            jnp.float32(0.0), jnp.int32(0))
    ts = jnp.arange(T, dtype=jnp.int32)
    final, traj = jax.lax.scan(step, init, (ts, ids, nxt))
    if trace_steps:
        return final[-2], final[-1], traj
    return final[-2], final[-1]


def _resolve_use_pallas(use_pallas: bool | None) -> bool:
    """None -> the backend default: Pallas kernel on TPU, jnp elsewhere."""
    return ops.on_tpu() if use_pallas is None else use_pallas


def simulate_jax(policy: str, ids: np.ndarray, costs: np.ndarray,
                 capacity_pages: int, num_objects: int | None = None,
                 sizes: np.ndarray | None = None,
                 use_pallas: bool | None = None):
    """Replay one policy on a uniform-size page trace. Returns (dollars, hits).

    `sizes` only affects the cost-density terms of GDS/GDSF/cost-Belady
    (the cache itself is page-uniform, matching the exact reference)."""
    ids = np.asarray(ids, dtype=np.int32)
    n = int(num_objects if num_objects is not None else ids.max() + 1)
    nxt = next_use_indices(ids).astype(np.int32)
    w = POLICY_WEIGHTS[policy].as_array()
    s = np.ones(n, np.float32) if sizes is None else np.asarray(sizes, np.float32)
    d, h = _simulate(jnp.asarray(ids), jnp.asarray(nxt),
                     jnp.asarray(costs, dtype=jnp.float32), jnp.asarray(s),
                     jnp.int32(capacity_pages), jnp.asarray(w), n,
                     _resolve_use_pallas(use_pallas))
    return float(d), int(h)


@functools.partial(jax.jit, static_argnames=("num_objects", "use_pallas"))
def _sweep_grid(weight_stack, ids, nxt, cost_matrix, sizes, budgets,
                num_objects: int, use_pallas: bool):
    """(Q policies x P prices x K budgets) grid as one compiled program."""

    def one(w, costs, B):
        d, _ = _simulate(ids, nxt, costs, sizes, B, w, num_objects,
                         use_pallas)
        return d

    f = jax.vmap(                                   # policies
        jax.vmap(                                   # price vectors
            jax.vmap(one, in_axes=(None, None, 0)),  # budgets
            in_axes=(None, 0, None)),
        in_axes=(0, None, None))
    return f(weight_stack, cost_matrix, budgets)


def sweep_jax(policy, ids: np.ndarray, cost_matrix: np.ndarray,
              budgets: np.ndarray, num_objects: int | None = None,
              sizes: np.ndarray | None = None,
              use_pallas: bool | None = None, tracer=None) -> np.ndarray:
    """Batched replay of a (policy x price-vector x budget) grid on device.

    policy:      one policy name -> dollars of shape (P, K);
                 a sequence of names / `PolicyWeights` (or a pre-stacked
                 (Q, 6) float array) -> dollars of shape (Q, P, K), all Q
                 policies replayed inside the SAME compiled scan program.
    cost_matrix: (P, N) per-object costs for P price vectors.
    budgets:     (K,) page budgets.
    tracer:      an `obs.Tracer` to record the call's phases as spans
                 (DESIGN.md §9): `replay.prepare` (next-use indices and host
                 arrays), `replay.transfer` (host to device), `replay.lower`
                 and `replay.compile` (first call of a shape only; the
                 compile span carries `cells`, `steps`, `mosaic_kernels`
                 and `scopes`, the step's `scope_map`), `replay.execute`
                 (dispatch until the output is ready) and `replay.fetch`
                 (device to host). None records nothing and builds no map.

    A shape is lowered and compiled (or loaded from the persistent cache)
    ahead of its first call, which makes compiling a phase of its own and
    its text readable; jit's dispatch then runs that same executable.
    """
    span = tracer.span if tracer else _no_span
    with span("replay.prepare", cat="replay"):
        single = isinstance(policy, str)
        if single:
            stack = stack_policy_weights([policy])
        elif isinstance(policy, np.ndarray) or isinstance(policy, jax.Array):
            stack = np.asarray(policy, dtype=np.float32)
            if stack.ndim != 2 or stack.shape[1] != 6:
                raise ValueError("weight stack must have shape (Q, 6)")
        else:
            stack = stack_policy_weights(policy)
        ids = np.asarray(ids, dtype=np.int32)
        n = int(num_objects if num_objects is not None else ids.max() + 1)
        nxt = next_use_indices(ids).astype(np.int32)
        up = _resolve_use_pallas(use_pallas)
    with span("replay.transfer", cat="replay"):
        s = (jnp.ones(n, jnp.float32) if sizes is None
             else jnp.asarray(sizes, jnp.float32))
        args = (jnp.asarray(stack), jnp.asarray(ids), jnp.asarray(nxt),
                jnp.asarray(cost_matrix, dtype=jnp.float32), s,
                jnp.asarray(budgets, dtype=jnp.int32))
        if tracer:
            jax.block_until_ready(args)
    key = (tuple((a.shape, a.dtype) for a in args), n, up)
    if key not in _EXECUTABLES:
        with span("replay.lower", cat="replay"):
            lowered = _sweep_grid.lower(*args, n, up)
        with span("replay.compile", cat="replay") as sp:
            compiled = lowered.compile()
            if tracer:
                text = compiled.as_text()
                sp.set(cells=len(stack) * len(cost_matrix) * len(budgets),
                       steps=len(ids),
                       mosaic_kernels=text.count("tpu_custom_call"),
                       scopes=_scope_map(text))
        if len(_EXECUTABLES) >= _MAX_EXECUTABLES:
            del _EXECUTABLES[next(iter(_EXECUTABLES))]
        _EXECUTABLES[key] = compiled
    with span("replay.execute", cat="replay"):
        out = jax.block_until_ready(_sweep_grid(*args, n, up))
    with span("replay.fetch", cat="replay"):
        out = np.asarray(out)
    return out[0] if single else out


def _no_span(name: str, cat: str = "span"):
    return contextlib.nullcontext()


def _scope_map(hlo: str) -> dict[str, list[str]]:
    from ..launch.hlo_analysis import scope_map
    return scope_map(hlo, STEP_SCOPES)


def step_scopes() -> dict[str, list[str]]:
    """Scope map of the grid program compiled last: each of `STEP_SCOPES`,
    and `unscoped`, -> the names of the compiled instructions it covers.

    A profiler names each device op event by its instruction, so the map
    turns a trace's op times into device time per phase of the scan step.
    Empty before any grid has compiled; built only when asked.
    """
    if not _EXECUTABLES:
        return {}
    return _scope_map(next(reversed(_EXECUTABLES.values())).as_text())
