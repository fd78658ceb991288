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

Budgets count pages (every object takes one: the paper's exact-reference
regime) or bytes (variable sizes, the CDN and memcache regime). Either
way a miss evicts victims until the object fits, in a `lax.while_loop`
that the grid's cells run in lockstep (under pages, one victim when the
cache is full), and an object larger than the budget is fetched through,
as in `policies.py`'s simulators.

Each scan step runs in `jax.named_scope`s (`STEP_SCOPES`): the score pass,
the eviction loop with its victim selections, the state update.
`step_scopes()` maps the compiled grid's instructions to them, so a
profiler trace's device time splits by phase of the step;
`sweep_jax(tracer=...)` records the host phases of each call as spans
(DESIGN.md §9).

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
from ..kernels.layout import LANES, SUBLANES, to_tiles

__all__ = ["PolicyWeights", "POLICY_WEIGHTS", "STEP_SCOPES", "cost_density",
           "simulate_jax", "sweep_jax", "stack_policy_weights", "step_scopes"]

_BIG = jnp.float32(3.4e38)
# entries of one (8, 128) tile of the scan's state
_TILE = SUBLANES * LANES
# the named scopes that partition the scan step: the touch bookkeeping and
# the score pass, the eviction loop with its victim selections, and the
# state update
STEP_SCOPES = ("replay.score", "replay.evict_bytes", "replay.update")
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


def cost_density(costs, sizes) -> np.ndarray:
    """c / s per object in float32, computed on the host: the chip's
    float32 division can round one ulp away from it, which moves GDSF's
    near-ties."""
    c = np.asarray(costs, np.float32)
    s = np.maximum(np.asarray(sizes, np.float32), np.float32(1e-30))
    return c / s


def _add_compensated(total, carry, x):
    """Kahan's compensated sum: (total + x, the part of it that float32
    rounding lost). Miss costs that span orders of magnitude then sum to
    the float32 rounding of their exact sum, not of a drifting one."""
    y = x - carry
    t = total + y
    return t, (t - total) - y


def _write_tile(table, k, mine, value):
    """`table` with `value` at the entries of its k-th (8, 128) tile where
    `mine` holds; the others keep what they held. The tile is sliced from
    the table's (rows // 8, 8, 128) view, a free reshape of the tiled
    layout, so the compiler knows the slice is aligned: at a row of the
    (rows, 128) table it cannot tell that the row is a multiple of 8, and
    copies each cell's tile as an unaligned slice, several times slower."""
    tiles = table.reshape(-1, SUBLANES, LANES)
    start = (k, 0, 0)
    tile = jax.lax.dynamic_slice(tiles, start, (1, SUBLANES, LANES))
    return jax.lax.dynamic_update_slice(
        tiles, jnp.where(mine, value, tile), start).reshape(table.shape)


def _evict_until_fits(select, cached, used, infl, miss, size, capacity,
                      sizes, gd_active, any_cell):
    """Eviction for one step's miss of an object that takes `size` of the
    budget. An object larger than the cache is fetched through: nothing is
    evicted and it is not inserted. Otherwise, while `used + size` passes
    `capacity`, evict the victim that `select(cached)` names and take its
    bytes off `used`.

    One trip evicts at most one object per cell. `any_cell` reduces the
    trip's predicate over the grid's cells, so the cells run the loop in
    lockstep, as many trips as the cell that needs most, and a cell that
    fits evicts nothing on a trip. A cell whose selection names no victim
    (score `_BIG` or NaN) stops needing trips, so the loop always ends.
    Returns (cached, used, infl, evictions, trips, insert): GreedyDual's L
    ends as the score of the step's last victim, and `insert` says whether
    the object goes in.
    """
    insert = miss & (size <= capacity)
    room = capacity - size

    def need(c):
        return insert & (c[1] > room) & ~c[5]

    def body(c):
        cached, used, infl, evicted, trips, stuck = c
        victim, victim_score = select(cached)
        found = victim_score < _BIG
        ev = need(c) & found
        out = (victim // LANES, victim % LANES)
        cached = cached.at[out].add(-ev.astype(jnp.int32))
        used = used - jnp.where(ev, sizes[victim], 0)
        infl = jnp.where(ev & gd_active, victim_score, infl)
        return (cached, used, infl, evicted + ev.astype(jnp.int32), trips + 1,
                stuck | (need(c) & ~found))

    out = jax.lax.while_loop(lambda c: any_cell(need(c)), body,
                             (cached, used, infl, jnp.int32(0), jnp.int32(0),
                              jnp.bool_(False)))
    return (*out[:5], insert)


@functools.partial(jax.jit,
                   static_argnames=("num_objects", "use_pallas", "trace_steps",
                                    "grid_axes"))
def _simulate(ids, nxt, costs, sizes, occupancy, c_over_s, capacity, weights,
              num_objects: int, use_pallas: bool = False,
              trace_steps: bool = False, grid_axes: tuple = ()):
    """One policy replay: (dollars, hits, evictions, fetch-throughs,
    eviction-loop trips).

    Victim = lexicographic argmin of (score, last_touch) over cached objects,
    where score = static (frozen at touch) + dynamic (Belady / cost-Belady,
    evaluated at eviction time from the stored next-use index). This exactly
    matches the heap key of the Python reference.

    `occupancy` (int32) is what each object takes of `capacity`: whole
    bytes, or 1 for a budget of pages; `sizes` (float32) enter only
    cost-Belady's score. A miss on an object larger than the cache is
    fetched through (billed, not inserted, nothing evicted), any other
    miss evicts victims in order until the object fits
    (`_evict_until_fits`, in scope `replay.evict_bytes`). `used` and the
    budget are int32, exact below 2**31; `used` never passes the budget,
    and the fit test compares `used` with the budget less the object's
    occupancy, so no sum can overflow. `grid_axes` names the vmap axes of
    a grid, over which the loop's predicate is reduced.

    The per-object state is kept as (rows, 128) tiles, object i at
    (i // 128, i % 128), laid out once before the scan as the victim kernel
    reads it; `cached` holds int32 0/1 flags, the kernel's mask, and the
    padding past `num_objects` is never cached. Costs and sizes are tiled
    for the whole-table score; the bill and the frozen score read one
    entry a step from the flat costs and `c_over_s` (`cost_density`,
    from the host). Dollars are a compensated float32 sum.

    `use_pallas` routes the victim argmin through the Pallas TPU kernel
    (`kernels.evict_argmin`) instead of the jnp reduction — the replay
    engine's eviction hot path on real TPUs. `trace_steps` additionally
    returns the per-step trajectory of the outputs but the trip count,
    for step-for-step equivalence tests.
    """
    T = ids.shape[0]
    if not (costs.shape == sizes.shape == occupancy.shape == (num_objects,)):
        raise ValueError(f"costs {costs.shape}, sizes {sizes.shape} and "
                         f"occupancy {occupancy.shape} must have one entry "
                         f"per object ({num_objects})")
    cost_tiles, _ = to_tiles(costs, ops.EVICT_BLOCK_N, 0.0)
    size_tiles, _ = to_tiles(sizes.astype(jnp.float32), ops.EVICT_BLOCK_N,
                             1.0)
    tiles = cost_tiles.shape
    INT_BIG = jnp.int32(2**31 - 1)
    # each entry's flat offset within an (8, 128) tile
    offset = jnp.arange(_TILE, dtype=jnp.int32).reshape(SUBLANES, LANES)
    gd_active = (weights[2] + weights[3]) > 0

    def total_scores(static, stored_nxt, t):
        """static + dynamic part, per object."""
        nxtf = stored_nxt.astype(jnp.float32)
        gap = jnp.maximum(nxtf - t, 1.0)
        never = stored_nxt >= T
        # belady: evict max next-use  -> score -nxt (never-reused = -BIG)
        bel = jnp.where(never, -_BIG, -nxtf)
        # cost-belady: evict max s*gap/c -> score -(s*gap/c); the floor
        # on c keeps a free object's ratio finite (whole-byte sizes and
        # steps stay below 2**31), so no other policy's zero weight meets
        # an infinity and makes a NaN
        cb = jnp.where(never, -_BIG,
                       -(size_tiles * gap / jnp.maximum(cost_tiles, 2.0**-64)))
        return static + weights[4] * bel + weights[5] * cb

    def victim_of(raw, touch, cached):
        """Lexicographic argmin of (score, last_touch, index) among cached
        objects: (flat index, score); score _BIG when none is cached."""
        if use_pallas:
            return ops.evict_argmin(raw, touch, cached, use_pallas=True)
        scores = jnp.where(cached != 0, raw, _BIG)
        victim_score = jnp.min(scores)
        tie = scores <= victim_score  # exact; _BIG rows excluded
        t_tie = jnp.where(tie, touch, INT_BIG)
        index = (jax.lax.broadcasted_iota(jnp.int32, tiles, 0) * LANES
                 + jax.lax.broadcasted_iota(jnp.int32, tiles, 1))
        victim = jnp.min(jnp.where(tie & (t_tie == jnp.min(t_tie)),
                                   index, INT_BIG))
        return victim, victim_score

    def any_cell(flag):
        if not grid_axes:
            return flag
        return jax.lax.pmax(flag.astype(jnp.int32), grid_axes) > 0

    def step(state, inp):
        (cached, static, stored_nxt, touch, freq, used, infl, dollars, lost,
         hits, counts) = state
        t, i, nu = inp
        with jax.named_scope("replay.score"):
            tf = t.astype(jnp.float32)
            at = (i // LANES, i % LANES)
            freq = freq.at[at].add(1)
            # the flag is read as a value of its own: fused into the bill's
            # per-cell update, the slice made XLA relay the whole table
            is_hit = jax.lax.optimization_barrier(cached[at]) != 0
            dollars, lost = _add_compensated(
                dollars, lost, jnp.where(is_hit, 0.0, costs[i]))
            hits = hits + is_hit.astype(jnp.int32)
            raw = total_scores(static, stored_nxt, tf)
        with jax.named_scope("replay.evict_bytes"):
            size_i = occupancy[i]
            # the mask is `cached` itself, which equals cached\{i}: a hit
            # evicts nothing, and a miss's i is not cached
            cached, used, infl, evicted, trips, do_insert = \
                _evict_until_fits(lambda c: victim_of(raw, touch, c),
                                  cached, used, infl, ~is_hit, size_i,
                                  capacity, occupancy, gd_active, any_cell)
        with jax.named_scope("replay.update"):
            used = used + jnp.where(do_insert, size_i, 0)
            bypass = ~is_hit & ~do_insert
            counts = (counts[0] + evicted, counts[1] + bypass.astype(jnp.int32),
                      counts[2] + trips)
            my_static = _static_score(weights, tf, freq[at].astype(jnp.float32),
                                      infl, c_over_s[i])
            # a hit leaves i cached and a miss inserts it (never as the
            # victim, which is cached on a miss); touches (hit or insert)
            # refresh score, next-use and touch time. The flag and the
            # score go in as the aligned (8, 128) tile that holds i, whose
            # start row every cell shares: one in-place slice update of
            # each table, where a single element makes a scatter of one
            # index a cell, or at the shared row a relayout of the whole
            # table. The tile comes from the loop's output, whose victims
            # may lie in it; a fetched-through cell keeps its entry.
            mine = (offset == i % _TILE) & (is_hit | do_insert)
            cached = _write_tile(cached, i // _TILE, mine, 1)
            static = _write_tile(static, i // _TILE, mine, my_static)
            stored_nxt = stored_nxt.at[at].set(nu)
            touch = touch.at[at].set(t)
        new_state = (cached, static, stored_nxt, touch, freq, used, infl,
                     dollars, lost, hits, counts)
        return new_state, ((dollars, hits, *counts[:2]) if trace_steps
                           else None)

    zero = jnp.int32(0)
    init = (jnp.zeros(tiles, jnp.int32), jnp.full(tiles, _BIG, jnp.float32),
            jnp.full(tiles, T, jnp.int32), jnp.zeros(tiles, jnp.int32),
            jnp.zeros(tiles, jnp.int32), zero, jnp.float32(0.0),
            jnp.float32(0.0), jnp.float32(0.0), zero, (zero, zero, zero))
    ts = jnp.arange(T, dtype=jnp.int32)
    final, traj = jax.lax.scan(step, init, (ts, ids, nxt))
    out = (final[7], final[9], *final[10])
    return (*out, traj) if trace_steps else out


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
    d, h, *_ = _simulate(jnp.asarray(ids), jnp.asarray(nxt),
                         jnp.asarray(costs, dtype=jnp.float32), jnp.asarray(s),
                         jnp.ones(n, jnp.int32),
                         jnp.asarray(cost_density(costs, s)),
                         jnp.int32(capacity_pages), jnp.asarray(w), n,
                         _resolve_use_pallas(use_pallas))
    return float(d), int(h)


# names of the grid's vmap axes, over which the eviction loop reduces its
# predicate
_GRID_AXES = ("policy", "price", "budget")


@functools.partial(jax.jit, static_argnames=("num_objects", "use_pallas"))
def _sweep_grid(weight_stack, ids, nxt, cost_matrix, density, sizes,
                occupancy, budgets, num_objects: int, use_pallas: bool):
    """(Q policies x P prices x K budgets) grid as one compiled program:
    dollars, evictions and fetch-throughs (Q, P, K), and the eviction
    loop's trips (one count for all cells)."""
    def one(w, costs, cos, B):
        out = _simulate(ids, nxt, costs, sizes, occupancy, cos, B, w,
                        num_objects, use_pallas, grid_axes=_GRID_AXES)
        return (out[0], *out[2:])

    policy, price, budget = _GRID_AXES
    f = jax.vmap(
        jax.vmap(
            jax.vmap(one, in_axes=(None, None, None, 0), axis_name=budget),
            in_axes=(None, 0, 0, None), axis_name=price),
        in_axes=(0, None, None, None), axis_name=policy)
    out = f(weight_stack, cost_matrix, density, budgets)
    return (*out[:3], out[3][0, 0, 0])


_BUDGET_UNITS = ("pages", "bytes")


def sweep_jax(policy, ids: np.ndarray, cost_matrix: np.ndarray,
              budgets: np.ndarray, num_objects: int | None = None,
              sizes: np.ndarray | None = None,
              use_pallas: bool | None = None, tracer=None,
              budget_unit: str = "pages") -> np.ndarray:
    """Batched replay of a (policy x price-vector x budget) grid on device.

    policy:      one policy name -> dollars of shape (P, K);
                 a sequence of names / `PolicyWeights` (or a pre-stacked
                 (Q, 6) float array) -> dollars of shape (Q, P, K), all Q
                 policies replayed inside the SAME compiled scan program.
    cost_matrix: (P, N) per-object costs for P price vectors.
    budgets:     (K,) budgets in `budget_unit`.
    budget_unit: what a budget counts, and so what each object takes of
                 it: "pages" (default), one page each, `sizes` entering
                 only the cost densities; "bytes", its `sizes` (required,
                 whole bytes below 2**31, as the budgets). A miss evicts
                 until the object fits; an object larger than the budget
                 (any object, under a budget of 0 pages) is fetched
                 through.
    tracer:      an `obs.Tracer` to record the call's phases as spans
                 (DESIGN.md §9): `replay.prepare` (next-use indices, cost
                 densities and host arrays), `replay.transfer` (host to
                 device), `replay.lower` and `replay.compile` (first call of
                 a shape only; the compile span carries `cells`, `steps`,
                 `mosaic_kernels` and `scopes`, the step's `scope_map`),
                 `replay.execute` (dispatch until the output is ready; it
                 carries `evict_trips`, the eviction loop's trips, and
                 `evictions` and `bypasses`, summed over the cells) and
                 `replay.fetch` (device to host). None
                 records nothing and builds no map.

    A shape is lowered and compiled (or loaded from the persistent cache)
    ahead of its first call, which makes compiling a phase of its own and
    its text readable; jit's dispatch then runs that same executable.
    """
    if budget_unit not in _BUDGET_UNITS:
        raise ValueError(f"budget_unit must be one of {_BUDGET_UNITS}, "
                         f"not {budget_unit!r}")
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
        if budget_unit == "bytes":
            sizes = _whole_bytes(sizes, "sizes")
            _whole_bytes(budgets, "budgets")
            occupancy = sizes
        else:
            occupancy = np.ones(n, np.int32)
        s = np.ones(n, np.float32) if sizes is None else np.asarray(sizes)
        density = cost_density(cost_matrix, s)
    with span("replay.transfer", cat="replay"):
        args = (jnp.asarray(stack), jnp.asarray(ids), jnp.asarray(nxt),
                jnp.asarray(cost_matrix, dtype=jnp.float32),
                jnp.asarray(density), jnp.asarray(s, jnp.float32),
                jnp.asarray(occupancy, jnp.int32),
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
    with span("replay.execute", cat="replay") as sp:
        out, evictions, bypasses, trips = jax.block_until_ready(
            _sweep_grid(*args, n, up))
        if tracer:
            sp.set(evict_trips=int(trips), evictions=int(evictions.sum()),
                   bypasses=int(bypasses.sum()))
    with span("replay.fetch", cat="replay"):
        out = np.asarray(out)
    return out[0] if single else out


def _whole_bytes(x, what: str) -> np.ndarray:
    """`x` as int32 whole bytes; raises unless every entry is one below
    2**31."""
    if x is None:
        raise ValueError(f"a byte budget needs {what}")
    a = np.asarray(x)
    if not (np.all(a >= 0) and np.all(a < 2**31) and np.all(a == np.round(a))):
        raise ValueError(f"{what} must be whole bytes in [0, 2**31)")
    return a.astype(np.int32)


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
