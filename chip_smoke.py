"""Smoke run of the main path on one TPU chip. Not a benchmark: the times it
prints are one cold run, compilation included.

    python chip_smoke.py [--seed 0]

One process, one chip, three phases, each checked against its reference:

  A. replay grid: `sweep_jax` over 6 policies x 4 price vectors x 4 page
     budgets, 2^20 objects, 8,192 Zipf requests, with the eviction kernel
     compiled by Mosaic. Dollars equal the jnp path cell for cell, and the
     host reference (`core.policies.simulate`) on a sub-grid.
  B. OPT-dollar bracket: `cost_foo(validate=True)` on the 60k-request CDN
     window under S3 internet prices. The schedule check runs through the
     compiled occupancy kernel, whose worst excess over the cap equals a
     numpy prefix sum of the same deltas.
  C. governed serving at full width: phi4-mini-3.8b with seeded random
     weights behind `ServeEngine(govern=True)`. Later rounds hit the
     prefix cache, the audit's dollars equal the store's meter, and the
     prefill logits of prompt + generated tokens agree with the last
     decode step's.

Exits non-zero, and prints no result, when JAX finds no TPU. The last line
of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# prefill vs decode logits: 16 bf16 roundings (2^-8 each) of the largest
# logit, for two orders of the same bf16 computation over 32 layers
LOGIT_RTOL = 16 * 2.0 ** -8


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def device_gate():
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {d.platform!r}")
    log(f"device platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    return d, len(devices)


def replay_grid(seed: int, n_objects: int, n_requests: int) -> None:
    """Phase A: the 96-cell policy x price x budget grid."""
    from repro.core import Trace, simulate, zipf_trace
    from repro.core.policies_jax import POLICY_WEIGHTS, sweep_jax
    from repro.kernels import ops
    from repro.obs import Tracer

    ids = zipf_trace(n_objects=n_objects, n_requests=n_requests,
                     seed=seed).ids
    rng = np.random.default_rng(seed)
    # power-of-two costs: every score and every partial dollar sum of the
    # first price vector is exact in float32, so the device grid must equal
    # the float64 host reference bit for bit there
    costs = 2.0 ** rng.integers(0, 12, n_objects)
    cost_matrix = np.stack([costs * 10.0 ** k for k in range(4)])
    budgets = np.array([16, 32, 64, 128])
    policies = list(POLICY_WEIGHTS)
    log(f"A: N={n_objects} T={n_requests} grid={len(policies)}x"
        f"{len(cost_matrix)}x{len(budgets)}")

    def run(use_pallas):
        """One grid answer; its dollars and the program's kernel count."""
        tracer = Tracer()
        t0 = time.perf_counter()
        out = sweep_jax(policies, ids, cost_matrix, budgets,
                        num_objects=n_objects, use_pallas=use_pallas,
                        tracer=tracer)
        wall = time.perf_counter() - t0
        dur = {sp.name: sp.dur for sp in tracer.spans()}
        kernels = sum(sp.attrs["mosaic_kernels"]
                      for sp in tracer.spans(name="replay.compile"))
        log(f"A: {'jnp' if use_pallas is False else 'kernel'} path "
            f"wall_s={wall:.3f} compile_s="
            f"{dur.get('replay.lower', 0.0) + dur.get('replay.compile', 0.0):.3f}"
            f" execute_s={dur['replay.execute']:.3f} "
            f"tpu_custom_call={kernels}")
        return out, kernels

    got, kernels = run(None)
    if ops.on_tpu():   # a CPU rehearsal runs the Pallas interpreter
        check(kernels > 0, "grid ran without the kernel")
    want, _ = run(False)
    check(got.shape == (len(policies), len(cost_matrix), len(budgets))
          and np.isfinite(got).all(), f"grid result shape {got.shape}")
    np.testing.assert_array_equal(got, want)
    log(f"A: kernel == jnp path on all {got.size} cells")

    trace = Trace(ids=ids, sizes=np.ones(n_objects))
    t0 = time.perf_counter()
    cells = 0
    for q, policy in enumerate(policies):
        for k in (0, len(budgets) - 1):
            host = simulate(policy, trace, cost_matrix[0], float(budgets[k]))
            check(got[q, 0, k] == np.float32(host.dollars),
                  f"{policy} B={budgets[k]}: device {got[q, 0, k]!r} "
                  f"host {host.dollars!r}")
            cells += 1
    log(f"A: kernel == host reference on {cells} cells "
        f"(price 0, budgets {budgets[0]} and {budgets[-1]}) "
        f"host_s={time.perf_counter() - t0:.3f}")


def opt_bracket(n_objects: int, n_requests: int) -> None:
    """Phase B: cost-FOO with the occupancy check on the device."""
    import jax

    from repro.core import PRICE_VECTORS, cost_foo, miss_costs, wiki_cdn_like
    from repro.kernels import ops

    trace = wiki_cdn_like(n_objects=n_objects, n_requests=n_requests, seed=0)
    costs = miss_costs(trace.sizes, PRICE_VECTORS["s3_internet"])
    B = float(trace.sizes.sum() * 0.02)
    log(f"B: T={n_requests} objects={n_objects} B={B:.0f} bytes "
        f"price=s3_internet")

    # record what cost_foo hands the occupancy kernel, and what the
    # compiler made of that call
    seen: dict = {}
    kernel = ops.occupancy_feasible

    def recording(deltas, zcap, **kw):
        occ, excess = kernel(deltas, zcap, **kw)
        seen.update(deltas=np.asarray(deltas), zcap=np.asarray(zcap),
                    hlo=jax.jit(lambda d, z: kernel(d, z, **kw))
                    .lower(deltas, zcap).compile().as_text())
        return occ, excess

    ops.occupancy_feasible = recording
    try:
        t0 = time.perf_counter()
        res = cost_foo(trace, costs, B, policies=("gdsf",), validate=True)
        wall = time.perf_counter() - t0
    finally:
        ops.occupancy_feasible = kernel
    p = res.profile
    log(f"B: wall_s={wall:.3f} lp_s={p['lp_seconds']:.3f} "
        f"round_s={p['round_seconds']:.3f} epochs={p['epochs']} "
        f"lower={res.lower:.9g} upper={res.upper:.9g} "
        f"bracket={res.bracket:.6g}")
    check("deltas" in seen, "cost_foo never reached the occupancy kernel")
    n_calls = seen["hlo"].count("tpu_custom_call")
    log(f"B: occupancy kernel tpu_custom_call={n_calls}")
    if ops.on_tpu():
        check(n_calls > 0, "occupancy check ran without the kernel")
    check(res.lower <= res.upper, "bracket lower > upper")
    occ = np.cumsum(seen["deltas"].astype(np.float64))
    want = float((occ - seen["zcap"].astype(np.float64)).max())
    got, tol = p["validate_excess"], p["validate_tol"]
    log(f"B: excess kernel={got:.9g} numpy={want:.9g} tol={tol:.6g}")
    check(abs(got - want) <= tol, "kernel excess disagrees with numpy")


def governed_serving(cfg, seed: int, prompt_len: int, new_tokens: int,
                     rounds: int) -> None:
    """Phase C: ServeEngine(govern=True) with the model at `cfg`'s widths."""
    import jax
    import jax.numpy as jnp

    from repro.models.registry import get_model
    from repro.serve import Request, ServeEngine

    model = get_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.key(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"C: {cfg.name} params={n_params} init_s="
        f"{time.perf_counter() - t0:.3f}")

    engine = ServeEngine(model, params, prefix_cache_bytes=1 << 26,
                         policy="gdsf", govern=True, governor_window=8)
    rng = np.random.default_rng(seed)
    hot = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
           for _ in range(3)]
    rid = 0
    last = []
    for r in range(rounds):
        cold = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
        last = [Request(rid + i, p, max_new_tokens=new_tokens)
                for i, p in enumerate(hot + [cold])]
        rid += len(last)
        t0 = time.perf_counter()
        engine.serve(last)
        log(f"C: round {r} requests={len(last)} wall_s="
            f"{time.perf_counter() - t0:.3f} hits={engine.cache.hits} "
            f"misses={engine.cache.misses}")
    check(all(len(q.output) == new_tokens for q in last), "output length")
    # round 0 stores the hot prefixes, round 1 fetches each once (billed),
    # every later round hits the local cache
    check(engine.cache.misses == len(hot)
          and engine.cache.hits == len(hot) * (rounds - 2),
          "prefix cache hits/misses")

    audit = engine.audit()
    meter = engine.store.consumer_snapshot()["serve_prefix_cache"]["dollars"]
    log(f"C: audit observed=${audit.observed_dollars:.9g} meter=${meter:.9g} "
        f"opt=[{audit.opt_dollars_lower:.9g}, {audit.opt_dollars_upper:.9g}]")
    check(audit.observed_dollars == meter, "audit dollars != meter")

    # prefill over prompt + generated tokens (last excluded) ends at the
    # position the engine's last decode step wrote; decoding that token
    # again from the prefill's cache must give the same logits, for every
    # request of the last round
    tokens = np.stack([np.concatenate([q.prompt, q.output[:-1]])
                       for q in last])
    t0 = time.perf_counter()
    logits_pre, caches = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(tokens)})
    logits_dec, _ = jax.jit(model.decode_step)(
        params, jnp.asarray(tokens[:, -1]), caches,
        jnp.int32(tokens.shape[1] - 1))
    pre = np.asarray(logits_pre, np.float32)
    dec = np.asarray(logits_dec, np.float32)
    check(np.isfinite(pre).all() and np.isfinite(dec).all(), "logits finite")
    rel = np.abs(pre - dec).max(axis=1) / np.abs(dec).max(axis=1)
    log(f"C: logits prefill vs decode, max |diff| / max |logit| per "
        f"request={[float(f'{r:.6g}') for r in rel]} "
        f"(limit {LOGIT_RTOL:.6g}) "
        f"check_s={time.perf_counter() - t0:.3f}")
    check((rel <= LOGIT_RTOL).all(), "prefill and decode logits differ")
    # the top-1 token is decidable only where the top-2 margin exceeds the
    # disagreement; seeded random bf16 weights leave near-ties among 200k
    # logits, so the token check takes the request with the widest margin
    top2 = np.sort(dec, axis=1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    b = int(margin.argmax())
    tops = (int(pre[b].argmax()), int(dec[b].argmax()),
            int(last[b].output[-1]))
    log(f"C: top-1 of request {b} (margin {margin[b]:.6g}): prefill, "
        f"decode, engine = {tops}")
    check(tops[0] == tops[1] == tops[2], "top-1 tokens differ")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"C: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device, count = device_gate()
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    for name, phase in (
            ("A", lambda: replay_grid(args.seed, 1 << 20, 8192)),
            ("B", lambda: opt_bracket(18_000, 60_000)),
            ("C", lambda: governed_serving(get_config("phi4-mini-3.8b"),
                                           args.seed, 128, 16, 4))):
        t0 = time.perf_counter()
        phase()
        gc.collect()   # drop the phase's device arrays before the next
        log(f"phase {name} passed wall_s={time.perf_counter() - t0:.3f}")

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
