"""Sharded lowering smoke: a miniature version of the production dry-run
on an 8-device host mesh, run in a subprocess (device count must be set
before jax initializes, and the main test process keeps 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.launch.roofline import normalize_cost_analysis
    from repro.models import build_model
    from repro.models.zoo import input_specs
    from repro.train.optimizer import AdamWConfig, adamw_init, opt_state_specs
    from repro.train.trainer import make_train_step

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    arch = os.environ["ARCH"]
    cfg = configs.reduced(configs.get(arch))
    par = ParallelConfig()
    model = build_model(cfg, par)
    shape = ShapeConfig("tiny_train", seq_len=32, global_batch=4,
                        kind="train")
    sds, ps = input_specs(cfg, shape, par)

    def ns(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_sds = jax.eval_shape(adamw_init, params_sds)
    step = make_train_step(model, AdamWConfig())
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(ns(model.param_specs()),
                          ns(opt_state_specs(model.param_specs())),
                          ns(ps)),
        ).lower(params_sds, opt_sds, sds)
        compiled = lowered.compile()
        ca = normalize_cost_analysis(compiled.cost_analysis())
    assert ca.get("flops", 0) > 0
    print("SHARDED-OK", arch, int(ca["flops"]))
""")


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen3-moe-30b-a3b",
                                  "mamba2-2.7b"])
def test_sharded_train_step_lowers(arch):
    env = dict(os.environ, ARCH=arch,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert f"SHARDED-OK {arch}" in out.stdout, out.stderr[-2000:]
