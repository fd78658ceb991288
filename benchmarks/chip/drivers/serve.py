"""Governed-serving driver: an open loop of requests at a fixed rate into
the system's `ServeEngine`, FIFO batches of up to `max_batch` queued
requests per `serve` call, with the governor's windowed audit run after
each call once `audit_every` prefix-cache accesses have accrued.

Set-up makes the weights on the device from the seed, builds the engine,
serves every batch size once, and (for a shared-prompt mix) serves every
prompt of the pool once so that the store holds each prefix: the window
starts with a warm store and an empty local cache.

End to end: serve_p50_ms and serve_p90_ms over every request of the window,
each the completion time minus the time the request was due. Checked:
sampled requests' served tokens against the configuration's plain float32
reference (the widest gap by which a served token's logit lies below the
reference's best), the store's bill against a recount of its GETs, and the
system's offline audit against the bill.
"""
from __future__ import annotations

import gc
import resource
import sys
import time

import numpy as np

from harness import gen, stats
from harness.counts import decoder_flops_per_token
from harness.seeds import jax_key, rng
from harness.weights import decoder_weights
from harness.window import CompileCounter, Window


class State:
    pass


def _arch(config: dict):
    from repro.models.common import ArchConfig
    return ArchConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=config["rope_theta"],
        rope_fraction=config["partial_rotary_factor"])


def setup(cell, seed: int, seconds: float, tracing: bool):
    import jax
    from repro.core import exact_opt_uniform_sweep
    from repro.egress.store import ObjectStore
    from repro.models.registry import get_model
    from repro.obs.trace import Tracer
    from repro.serve import Request, ServeEngine

    c, tr = cell.config, cell.traffic
    s = State()
    s.cell, s.seed, s.Request = cell, seed, Request
    s.weights = decoder_weights(c, jax_key(seed, "weights"))
    model = get_model(_arch(c))
    want = model.abstract()
    if jax.tree.structure(want) != jax.tree.structure(s.weights) or any(
            (a.shape, a.dtype) != (w.shape, w.dtype) for a, w in
            zip(jax.tree.leaves(want), jax.tree.leaves(s.weights))):
        raise RuntimeError("the system declares another weight layout")
    sv = c["serving"]
    s.tracer = Tracer(max_spans=1_000_000) if tracing else None
    s.engine = ServeEngine(model, s.weights, store=ObjectStore(sv["store_price"]),
                           prefix_cache_bytes=sv["prefix_cache_bytes"],
                           policy=sv["policy"], govern=sv["govern"],
                           governor_window=sv["governor_window"],
                           tracer=s.tracer)
    s.due, s.which, s.table = gen.schedule(tr, seconds, c["vocab_size"], seed)
    S, new, B = tr["prompt_tokens"], tr["new_tokens"], tr["max_batch"]
    # warm-up: every batch size once; a shared pool is then served whole,
    # so each of its prefixes is in the store before the window
    if tr["sharing"]["kind"] == "pool":
        warm = list(s.table)
    else:
        warm = list(rng(seed, "warmup").integers(
            0, c["vocab_size"], (B * (B + 1) // 2, S), dtype=np.int32))
    sizes = list(range(1, B + 1))
    rid = -1
    while warm:
        b = sizes.pop(0) if sizes else B
        batch, warm = warm[:b], warm[b:]
        s.engine.serve([Request(rid - j, p, max_new_tokens=new)
                        for j, p in enumerate(batch)])
        rid -= len(batch)
    # the audit's exact solver, once, outside the window
    exact_opt_uniform_sweep(np.array([0, 1, 0], np.int32),
                            np.ones(2), np.array([1]))
    s.compiles = CompileCounter()
    return s


def window(s, seconds: float, trace_dir) -> Window:
    import jax
    from harness.profile import Capture, reduce_trace
    tr, c = s.cell.traffic, s.cell.config
    n, B, new = len(s.due), tr["max_batch"], tr["new_tokens"]
    S = tr["prompt_tokens"]
    reqs = [s.Request(i, s.table[s.which[i]], max_new_tokens=new)
            for i in range(n)]
    done = np.full(n, np.nan)
    late = []            # how late the loop woke for a due request (s)
    serve_s, audits, batches = 0.0, 0, 0
    eng = s.engine
    accesses = lambda: eng.cache.hits + eng.cache.misses
    mark = accesses()
    span0 = len(s.tracer.to_dicts()) if s.tracer else 0
    cap = Capture(trace_dir) if trace_dir is not None else None
    traced_steps = None
    failed = 0
    s.compiles.active = True
    if cap:
        cap.__enter__()
    t0 = time.perf_counter()
    nxt = 0
    queue: list[int] = []
    max_queue = 0
    while nxt < n or queue:
        now = time.perf_counter() - t0
        while nxt < n and s.due[nxt] <= now:
            queue.append(nxt)
            nxt += 1
        if not queue:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, s.due[nxt] - now))
            late.append(time.perf_counter() - t0 - s.due[nxt])
            continue
        max_queue = max(max_queue, len(queue))
        batch, queue = queue[:B], queue[B:]
        t1 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.serve"):
                eng.serve([reqs[i] for i in batch])
        except Exception as e:          # a failed call fails its requests
            failed += len(batch)
            print(f"[bench] serve failed: {e!r}", file=sys.stderr, flush=True)
        t2 = time.perf_counter()
        serve_s += t2 - t1
        batches += 1
        done[batch] = t2 - t0
        if accesses() - mark >= tr["audit_every"] and eng.governor:
            with jax.profiler.TraceAnnotation("bench.audit"):
                eng.governor.audit()
            audits += 1
            mark = accesses()
        if cap and traced_steps is None and t2 - t0 >= tr["trace_seconds"]:
            cap.__exit__(None, None, None)
            traced_steps = batches * (new - 1)
    elapsed = time.perf_counter() - t0
    s.compiles.active = False
    if cap and traced_steps is None:
        cap.__exit__(None, None, None)
        traced_steps = batches * (new - 1)
    ok = [r.output is not None and len(r.output) == new for r in reqs]
    failed = max(failed, n - sum(ok))
    lat_ms = 1e3 * (done - s.due)
    lat_ms = lat_ms[np.isfinite(lat_ms)]
    flops = n * (sum(decoder_flops_per_token(c, k + 1, k == S - 1)
                     for k in range(S))
                 + sum(decoder_flops_per_token(c, S + k + 1, True)
                       for k in range(new - 1)))
    red = None
    if cap:
        import shutil
        red = reduce_trace(cap.path)
        shutil.rmtree(trace_dir, ignore_errors=True)
    spans = s.tracer.to_dicts()[span0:] if s.tracer else []
    late = np.asarray(late) if late else np.zeros(1)
    return Window(
        end_to_end={"serve_p50_ms": stats.percentile(lat_ms, 50),
                    "serve_p90_ms": stats.percentile(lat_ms, 90)},
        attempted=n, failed=failed,
        counters={"decode_steps_traced": traced_steps or 0,
                  "serve_wall_s": serve_s, "model_flops": flops,
                  "batches": batches, "audits": audits,
                  "max_queue": max_queue,
                  "drain_s": float(np.nanmax(done) - s.due[-1]),
                  "compiles_in_window": s.compiles.count},
        spans=spans, series=eng.metrics.snapshot()["series"], trace=red,
        outputs=reqs,
        notes=[f"requests={n} batches={batches} audits={audits} "
               f"elapsed_s={elapsed!r} serve_s={serve_s!r} "
               f"hits={eng.cache.hits} misses={eng.cache.misses} "
               f"max_queue={max_queue} "
               f"drain_s={float(np.nanmax(done) - s.due[-1])!r} "
               f"compiles_in_window={s.compiles.count}",
               f"host maxrss_mb={resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024!r}",
               f"generator lateness: mean_ms={1e3 * float(late.mean())!r} "
               f"max_ms={1e3 * float(late.max())!r} over {len(late)} waits"])


def check(s, win: Window) -> list[dict]:
    c, tr = s.cell.config, s.cell.traffic
    eng = s.engine
    # the bill: every GET of a prefix blob costs f + blob_bytes * e
    sv = c["serving"]
    blob = (c["num_hidden_layers"] * tr["prompt_tokens"]
            * c["num_key_value_heads"]
            * (c["hidden_size"] // c["num_attention_heads"]) * 2)
    m = eng.store.meter
    recount = 0.0
    for _ in range(m.gets):
        recount += sv["store_get_fee"] + float(blob) * sv["store_egress_per_byte"]
    bill_diff = abs(m.dollars - recount) + abs(m.bytes_egressed - m.gets * blob)
    audit_diff = abs(eng.audit().observed_dollars
                     - eng.store.meter_for(eng.cache.consumer).dollars)
    # served tokens against the reference, on a seeded sample
    reqs = [r for r in win.outputs
            if r.output is not None and len(r.output) == tr["new_tokens"]]
    pick = rng(s.seed, "check").choice(len(reqs),
                                       min(tr["check_requests"], len(reqs)),
                                       replace=False)
    s.sample = [reqs[i] for i in sorted(pick)]
    s.engine = eng = None
    gc.collect()
    gap = reference_gap(s.cell, s.weights, s.sample)
    return [{"name": "logit_gap", "value": gap,
             "limit": c["limits"]["logit_gap"]},
            {"name": "bill_diff", "value": bill_diff, "limit": 0.0},
            {"name": "audit_diff", "value": audit_diff, "limit": 0.0},
            {"name": "window_compiles",
             "value": win.counters["compiles_in_window"], "limit": 0}]


def reference_gap(cell, weights, sample, fp8: bool = False) -> float:
    """Widest gap, over the sampled requests' served tokens, between the
    reference's best logit and its logit of the served token (fp8=False),
    or of the token the fp8 control puts first (fp8=True); each gap in
    units of the standard deviation of the reference's logits at that
    position, so that the limit does not depend on the logits' scale."""
    ref = cell.reference()
    S = cell.traffic["prompt_tokens"]
    toks = np.stack([np.concatenate([r.prompt, r.output[:-1]]) for r in sample])
    served = np.stack([r.output for r in sample])
    want = ref.logits_at(weights, cell.config, toks, S - 1)
    if fp8:
        served = ref.logits_at(weights, cell.config, toks, S - 1,
                               fp8=True).argmax(-1)
    got = np.take_along_axis(want, served[..., None], -1)[..., 0]
    return float(((want.max(-1) - got) / want.std(-1)).max())


def control(s, win: Window) -> dict:
    """The fp8 control's reading on the requests check() sampled."""
    return {"logit_gap": reference_gap(s.cell, s.weights, s.sample,
                                       fp8=True)}


def reschedule(s, rate: float, seconds: float, seed: int) -> None:
    """A new schedule at another rate, for the knee sweep (sweep.py)."""
    s.cell.traffic["rate_per_s"] = rate
    s.due, s.which, table = gen.schedule(
        s.cell.traffic, seconds, s.cell.config["vocab_size"], seed)
    if s.cell.traffic["sharing"]["kind"] != "pool":
        s.table = table      # a pool stays the one set-up stored
