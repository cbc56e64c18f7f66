"""§Perf optimization variants must preserve semantics:

* MoE ep_shardmap == gspmd dispatch (same math, different collectives),
  checked on an 8-device host mesh in a subprocess.
* master-weights mixed precision trains and tracks fp32 loss closely.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.trainer import make_train_step

_EP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.models import moe as moe_mod
    from repro.models.layers import ShardPlan

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = ShardPlan(dp=("data",), tp="model", fsdp="data")
    E, D, F, k = 8, 64, 128, 2
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    p = {
        "router": jax.random.normal(ks[0], (D, E)) * 0.1,
        "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.05,
        "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.05,
        "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.05,
    }
    x = jax.random.normal(ks[4], (4, 16, D))
    kw = dict(top_k=k, n_experts=E, capacity_factor=2.0, sh=sh,
              compute_dtype=jnp.float32, bulk_steal=True)
    with jax.set_mesh(mesh):
        base = jax.jit(lambda p, x: moe_mod.moe_apply(p, x, impl="gspmd", **kw))(p, x)
        opt = jax.jit(lambda p, x: moe_mod.moe_apply(p, x, impl="ep_shardmap", **kw))(p, x)
    np.testing.assert_allclose(np.asarray(base), np.asarray(opt),
                               atol=2e-5, rtol=2e-4)
    print("EP-PARITY-OK")
""")


_FD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.configs.base import ParallelConfig
    from repro.models import build_model
    import repro.models.transformer as tmod

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    par = ParallelConfig()
    base_cfg = configs.reduced(configs.get("llama3.2-1b"))
    tmod._SEQ_SHARD_MIN = 16   # force the seq-sharded decode path

    outs = {}
    for impl in ("gspmd", "flash_shardmap"):
        cfg = dataclasses.replace(base_cfg, decode_impl=impl)
        model = build_model(cfg, par)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 1,
                                  cfg.vocab_size, jnp.int32)
        with jax.set_mesh(mesh):
            logits, cache = jax.jit(model.prefill)(params, toks)
            cache = model.grow_cache(cache, 40)
            lg, cache = jax.jit(model.decode_step)(params, cache,
                                                   toks[:, :1])
            lg2, _ = jax.jit(model.decode_step)(params, cache, toks[:, 1:2])
        outs[impl] = (np.asarray(lg), np.asarray(lg2))
    np.testing.assert_allclose(outs["gspmd"][0], outs["flash_shardmap"][0],
                               atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(outs["gspmd"][1], outs["flash_shardmap"][1],
                               atol=3e-2, rtol=3e-2)
    print("FLASH-DECODE-PARITY-OK")
""")


def test_flash_decode_matches_gspmd():
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", _FD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "FLASH-DECODE-PARITY-OK" in out.stdout, out.stderr[-2000:]


def test_ep_shardmap_matches_gspmd():
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", _EP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "EP-PARITY-OK" in out.stdout, out.stderr[-2000:]


def test_master_weights_training_tracks_fp32():
    import dataclasses

    cfg32 = configs.reduced(configs.get("llama3.2-1b"))
    cfg16 = dataclasses.replace(cfg32, param_dtype="bfloat16")
    from repro.data.synthetic import synth_batch

    losses = {}
    for name, cfg, mw in (("fp32", cfg32, False), ("bf16", cfg16, True)):
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params, master_weights=mw)
        ocfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=20,
                           master_weights=mw)
        step = jax.jit(make_train_step(model, ocfg))
        for i in range(20):
            raw = synth_batch(0, 0, i, 8, 32, cfg.vocab_size)
            batch = {"tokens": jnp.asarray(raw["tokens"]),
                     "labels": jnp.asarray(raw["labels"])}
            params, opt, m = step(params, opt, batch)
        losses[name] = float(m["loss"])
    assert np.isfinite(losses["bf16"])
    # bf16-with-master must land within 5% of the fp32 loss
    assert abs(losses["bf16"] - losses["fp32"]) / losses["fp32"] < 0.05, losses
