"""Dry-run machinery on a mini 8-device host mesh (subprocess: the device
count must be set before jax initializes, so this can't run in-process)."""
import json
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.models.common import set_activation_mesh
    from repro.parallel.sharding import make_rules, params_sharding, batch_spec
    from repro.train.optim import OptimizerConfig, make_optimizer
    from repro.train.trainer import make_train_step, train_state_shardings
    from repro.launch.hlo_analysis import analyze_collectives
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    set_activation_mesh(mesh)
    cfg = get_config("gemma3-4b", smoke=True)
    model = get_model(cfg)
    rules = make_rules(mesh)
    opt = make_optimizer(OptimizerConfig())
    ps, osd, ap, aos = train_state_shardings(rules, model, opt)
    step = make_train_step(model, opt, microbatches=2, grad_shardings=ps)
    batch = model.train_inputs(8, 32)
    bs = batch_spec(rules, batch)
    with mesh:
        lowered = jax.jit(step, in_shardings=(ps, osd, bs),
                          out_shardings=(NamedSharding(mesh, P()), ps, osd),
                          donate_argnums=(0, 1)).lower(ap, aos, batch)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    cs = analyze_collectives(compiled.as_text())
    ma = compiled.memory_analysis()
    print(json.dumps({
        "flops": float(ca.get("flops", 0.0)),
        "coll_bytes": cs.total_bytes,
        "coll_count": cs.total_count,
        "temp_bytes": ma.temp_size_in_bytes,
    }))
""")


def test_mini_mesh_dryrun():
    out = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 1e6            # real per-device work counted
    assert rec["coll_count"] > 0         # SPMD emitted collectives
    assert rec["coll_bytes"] > 0
    assert rec["temp_bytes"] > 0