import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# This override exists ONLY for the dry-run (assignment spec); smoke tests
# and benchmarks see the real single CPU device.

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
#
# For each cell this proves the distribution config is coherent (sharding
# resolves, collectives lower, memory fits) and extracts the roofline terms:
#
#   python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k --mesh multi
#   python -m repro.launch.dryrun --all --out results/dryrun.jsonl
#
# Output: one JSON record per cell (memory_analysis, cost_analysis, collective
# bytes by kind, roofline terms). EXPERIMENTS.md §Dry-run/§Roofline read these.

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import count_params, model_flops, terms_from_analysis
from repro.models.registry import get_model
from repro.parallel.sharding import (batch_spec, kv_cache_sharding, make_rules,
                                     params_sharding)
from repro.train.optim import OptimizerConfig, make_optimizer
from repro.train.trainer import make_train_step, train_state_shardings

# optimizer-state memory is the binding constraint at 1T params (DESIGN.md §5)
OPTIMIZER_OVERRIDES = {
    "kimi-k2-1t-a32b": OptimizerConfig(name="adafactor"),
    "qwen2-vl-72b": OptimizerConfig(name="adamw", moment_dtype=jnp.bfloat16),
}
DEFAULT_OPT = OptimizerConfig(name="adamw")


def _opt_for(arch: str):
    return make_optimizer(OPTIMIZER_OVERRIDES.get(arch, DEFAULT_OPT))


def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _lower_opt_probe(opt, ap, ps, osd, mesh):
    """Standalone optimizer-update program (counted once per real step)."""
    import jax.numpy as _jnp
    grads = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, _jnp.bfloat16), ap)
    aos = jax.eval_shape(opt.init, ap)

    def upd(g, s, p):
        return opt.update(g, s, p)

    fn = jax.jit(upd, in_shardings=(ps, osd, ps),
                 out_shardings=(ps, osd), donate_argnums=(1, 2))
    return fn.lower(grads, aos, ap)


# grad-accumulation per train cell so activations fit 16 GB/chip
# (EXPERIMENTS.md §Dry-run documents the napkin math per arch)
MICROBATCH_OVERRIDES = {
    "default": 4,
    "xlstm-125m": 1,
    "qwen2-moe-a2.7b": 4,
    "gemma3-4b": 4,
    "whisper-large-v3": 4,
    "phi4-mini-3.8b": 8,
    "chatglm3-6b": 8,
    "recurrentgemma-9b": 8,
    "mistral-nemo-12b": 8,
    "qwen2-vl-72b": 16,
    "kimi-k2-1t-a32b": 16,
}
# the 1T cell can't afford an f32 grad accumulator (16 GB/chip alone)
ACCUM_DTYPE_OVERRIDES = {"kimi-k2-1t-a32b": jnp.bfloat16}


def lower_cell(arch: str, shape_id: str, mesh, *, moe_ep: bool = False,
               microbatches: int | None = None):
    """Returns (lowered, meta, probe) for one cell."""
    cfg = get_config(arch)
    model = get_model(cfg)
    rules = make_rules(mesh, moe_ep=moe_ep)
    shape = SHAPES[shape_id]
    kind = shape["kind"]
    B, S = shape["global_batch"], shape["seq_len"]
    dp = _dp_axes(mesh)
    if microbatches is None:
        microbatches = MICROBATCH_OVERRIDES.get(
            arch, MICROBATCH_OVERRIDES["default"])
        # keep every DP shard busy: at least one row per shard per microbatch
        from repro.parallel.sharding import mesh_axis_size
        microbatches = max(1, min(microbatches, B // mesh_axis_size(mesh, dp)))

    # probes reconstruct true per-step cost from scanned programs
    # (cost_analysis counts a while body ONCE; see run_cell):
    #   dense:  T = mb*P - (mb-1)*O
    #   moe:    T = mb*P + mb*(n_tail-1)*L1 - (mb-1)*O
    probes = {}
    accum_dtype = ACCUM_DTYPE_OVERRIDES.get(arch, jnp.float32)
    scan_layers = cfg.num_experts > 0 or (
        cfg.family in ("dense", "vlm", "moe") and cfg.num_layers >= 48)
    if kind == "train" and scan_layers:
        # Giants (MoE or >=48 homogeneous layers) train with the
        # scan-layers layout (compile-time at fleet scale; see
        # models/transformer.py). Roofline FLOPs use the hybrid
        # accounting: scan program counts the body once, the standalone
        # per-layer probe supplies the remaining (n-1) layers.
        from repro.models import transformer as tfm
        from repro.models.common import abstract_params, axes_tree
        opt = _opt_for(arch)
        defs = tfm.stacked_param_defs(cfg)
        ap = abstract_params(defs, cfg.param_dtype)
        ax = axes_tree(defs)
        ps = params_sharding(rules, ap, ax)
        aos = jax.eval_shape(opt.init, ap)
        from repro.train.trainer import opt_state_sharding
        osd = opt_state_sharding(rules, opt, ap, ax)

        step = make_train_step(
            model, opt, microbatches=microbatches, accum_dtype=accum_dtype,
            grad_shardings=ps,
            loss_override=lambda p, b: tfm.loss_fn_scanned(cfg, p, b))
        batch = model.train_inputs(B, S)
        bs = batch_spec(rules, batch)
        fn = jax.jit(step, in_shardings=(ps, osd, bs),
                     out_shardings=(NamedSharding(mesh, P()), ps, osd),
                     donate_argnums=(0, 1))
        lowered = fn.lower(ap, aos, batch)
        # per-layer fwd+bwd probe (at MICRO batch size) for layer-scan cost
        Bm = B // microbatches
        ldefs = tfm.layer_defs(cfg, cfg.first_k_dense)
        lap = abstract_params(ldefs, cfg.param_dtype)
        lps = params_sharding(rules, lap, axes_tree(ldefs))
        dp_b = rules._fit(Bm, dp)
        x_sds = jax.ShapeDtypeStruct((Bm, S, cfg.d_model), cfg.param_dtype)
        if cfg.mrope_sections:   # VLM: three position streams
            pos_sds = jax.ShapeDtypeStruct((3, Bm, S), jnp.int32)
            pos_sh = NamedSharding(mesh, P(None, dp_b, None))
        else:
            pos_sds = jax.ShapeDtypeStruct((Bm, S), jnp.int32)
            pos_sh = NamedSharding(mesh, P(dp_b, None))
        pfn = jax.jit(tfm.layer_fwdbwd_probe(cfg, cfg.first_k_dense),
                      in_shardings=(lps,
                                    NamedSharding(mesh, P(dp_b, None, None)),
                                    pos_sh))
        n_tail = cfg.num_layers - cfg.first_k_dense
        probes["layer"] = (pfn.lower(lap, x_sds, pos_sds),
                           microbatches * (n_tail - 1))
        if microbatches > 1:
            probes["opt"] = (_lower_opt_probe(opt, ap, ps, osd, mesh),
                             -(microbatches - 1))
    elif kind == "train":
        opt = _opt_for(arch)
        ps, osd, ap, aos = train_state_shardings(rules, model, opt)
        step = make_train_step(model, opt, microbatches=microbatches,
                               accum_dtype=accum_dtype, grad_shardings=ps)
        batch = model.train_inputs(B, S)
        bs = batch_spec(rules, batch)
        fn = jax.jit(step,
                     in_shardings=(ps, osd, bs),
                     out_shardings=(NamedSharding(mesh, P()), ps, osd),
                     donate_argnums=(0, 1))
        lowered = fn.lower(ap, aos, batch)
        if microbatches > 1:
            probes["opt"] = (_lower_opt_probe(opt, ap, ps, osd, mesh),
                             -(microbatches - 1))
    elif kind == "prefill":
        ap = model.abstract()
        ps = params_sharding(rules, ap, model.axes())
        batch = model.prefill_inputs(B, S)
        bs = batch_spec(rules, batch)
        abstract_caches = jax.eval_shape(
            lambda p, b: model.prefill(p, b)[1], ap, batch)
        cache_sh = kv_cache_sharding(rules, abstract_caches)
        logits_sh = NamedSharding(mesh, P(rules._fit(B, dp), None))
        fn = jax.jit(lambda p, b: model.prefill(p, b),
                     in_shardings=(ps, bs),
                     out_shardings=(logits_sh, cache_sh))
        lowered = fn.lower(ap, batch)
    elif kind == "decode":
        ap = model.abstract()
        ps = params_sharding(rules, ap, model.axes())
        caches = model.abstract_caches(B, S)
        cache_sh = kv_cache_sharding(rules, caches)
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        tok_sh = NamedSharding(mesh, P(rules._fit(B, dp)))
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        pos_sh = NamedSharding(mesh, P())
        logits_sh = NamedSharding(mesh, P(rules._fit(B, dp), None))
        fn = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i),
                     in_shardings=(ps, tok_sh, cache_sh, pos_sh),
                     out_shardings=(logits_sh, cache_sh),
                     donate_argnums=(2,))
        lowered = fn.lower(ap, tok, caches, pos)
    else:
        raise ValueError(kind)

    # model-level FLOP accounting for the useful-compute ratio
    total, active, embed = count_params(model.abstract(), model.axes(),
                                        top_k=cfg.top_k,
                                        num_experts=cfg.num_experts)
    tokens = B * S if kind in ("train", "prefill") else B
    mf = model_flops(kind, active, tokens)
    prog_mult = microbatches if kind == "train" else 1
    meta = dict(arch=arch, shape=shape_id, kind=kind, global_batch=B,
                seq_len=S, params_total=total, params_active=active,
                params_embed=embed, model_flops=mf,
                microbatches=microbatches, program_multiplier=prog_mult)
    return lowered, meta, probes


def run_cell(arch: str, shape_id: str, *, multi_pod: bool, moe_ep=False,
             microbatches=None):
    from repro.models.common import set_activation_mesh
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_activation_mesh(mesh)
    n_dev = mesh.size
    rec = dict(mesh="multi" if multi_pod else "single", devices=n_dev,
               moe_ep=moe_ep)
    t0 = time.time()
    with mesh:
        lowered, meta, probes = lower_cell(arch, shape_id, mesh,
                                           moe_ep=moe_ep,
                                           microbatches=microbatches)
        rec.update(meta)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        extra_flops = extra_bytes = 0.0
        rec["probes"] = {}
        for pname, (plow, mult) in probes.items():
            pc = plow.compile().cost_analysis()
            pf = float(pc.get("flops", 0.0))
            pb = float(pc.get("bytes accessed", 0.0))
            extra_flops += pf * mult
            extra_bytes += pb * mult
            rec["probes"][pname] = dict(multiplier=mult, flops=pf, bytes=pb)
    rec["lower_s"] = round(t1 - t0, 1)
    rec["compile_s"] = round(t2 - t1, 1)
    ma = compiled.memory_analysis()
    rec["memory"] = dict(
        argument_gib=ma.argument_size_in_bytes / 2**30,
        output_gib=ma.output_size_in_bytes / 2**30,
        temp_gib=ma.temp_size_in_bytes / 2**30,
        alias_gib=ma.alias_size_in_bytes / 2**30,
        peak_gib=(ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
    )
    ca = compiled.cost_analysis()
    pm = rec.get("program_multiplier", 1)
    flops = float(ca.get("flops", 0.0)) * pm + extra_flops
    byts = float(ca.get("bytes accessed", 0.0)) * pm + extra_bytes
    rec["cost"] = dict(flops_per_device=flops, bytes_per_device=byts,
                       program_flops=float(ca.get("flops", 0.0)))
    hlo = compiled.as_text()
    cs = analyze_collectives(hlo)
    rec["collectives"] = dict(bytes_by_kind=cs.bytes_by_kind,
                              count_by_kind=cs.count_by_kind,
                              total_bytes=cs.total_bytes)
    rt = terms_from_analysis(flops, byts, cs.total_bytes, n_dev,
                             rec["model_flops"])
    rec["roofline"] = rt.as_dict()
    rec["ok"] = True
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--moe-ep", action="store_true",
                    help="expert-parallel MoE variant (perf experiment)")
    ap.add_argument("--microbatch", type=int, default=None,
                    help="override grad-accumulation microbatches")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    todo = []
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        for a, s in cells():
            for m in meshes:
                todo.append((a, s, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for m in meshes:
            todo.append((args.arch, args.shape, m))

    outpath = pathlib.Path(args.out) if args.out else None
    done = set()
    if outpath and outpath.exists() and args.skip_existing:
        for line in outpath.read_text().splitlines():
            try:
                r = json.loads(line)
                if r.get("ok"):
                    done.add((r["arch"], r["shape"], r["mesh"],
                              r.get("moe_ep", False)))
            except json.JSONDecodeError:
                pass

    for arch, shape_id, multi in todo:
        key = (arch, shape_id, "multi" if multi else "single", args.moe_ep)
        if key in done:
            print(f"SKIP {key}")
            continue
        print(f"=== {arch} x {shape_id} x "
              f"{'multi' if multi else 'single'} ===", flush=True)
        try:
            rec = run_cell(arch, shape_id, multi_pod=multi,
                           moe_ep=args.moe_ep, microbatches=args.microbatch)
            print(f"  ok compile={rec['compile_s']}s "
                  f"peak={rec['memory']['peak_gib']:.2f}GiB "
                  f"flops/dev={rec['cost']['flops_per_device']:.3e} "
                  f"coll={rec['collectives']['total_bytes']:.3e}B "
                  f"dominant={rec['roofline']['dominant']}", flush=True)
        except Exception as e:
            rec = dict(arch=arch, shape=shape_id,
                       mesh="multi" if multi else "single",
                       moe_ep=args.moe_ep, ok=False, error=str(e),
                       traceback=traceback.format_exc()[-2000:])
            print(f"  FAIL {e}", flush=True)
        if outpath:
            with outpath.open("a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()