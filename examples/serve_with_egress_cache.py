"""Serving example: batched greedy decoding with an egress-billed prefix
cache. Repeated prompts re-fetch their prefix KV from cloud storage unless
the dollar-aware cache retains them; the audit scores the realized bill
against the exact offline reference.

    PYTHONPATH=src python examples/serve_with_egress_cache.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.models.registry import get_model
from repro.serve.engine import Request, ServeEngine
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = get_config("gemma3-4b", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, prefix_cache_bytes=1 << 22,
                         policy="gdsf", govern=True, governor_window=8)

    rng = np.random.default_rng(0)
    # a few hot prompts (shared prefixes) + a stream of cold ones, served in
    # rounds so repeats of a hot prefix touch the egress-billed prefix cache
    hot = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
           for _ in range(3)]
    done = []
    rid = 0
    for round_ in range(6):
        reqs = [Request(rid + i, h, max_new_tokens=4)
                for i, h in enumerate(hot)]
        rid += len(hot)
        cold = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        reqs.append(Request(rid, cold, max_new_tokens=4)); rid += 1
        done += engine.serve(reqs)
    print(f"served {len(done)} requests; sample output: "
          f"{done[0].output.tolist()}")
    print("\n--- prefix-cache egress audit ---")
    print(engine.audit().summary())
    print(f"store meter: {engine.store.meter.snapshot()}")
    print("\n--- online governance ---")
    win = engine.governor.audit()
    if win is not None:
        print(win.summary())
    gov = engine.governor.snapshot()
    print(f"governor: policy={gov['policy']} swaps={len(gov['swaps'])} "
          f"shadow $: " + ", ".join(f"{p}={s['dollars']:.6f}"
                                    for p, s in gov['shadow'].items()))


if __name__ == "__main__":
    main()