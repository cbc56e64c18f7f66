"""`sharded_superstep` on a real mesh axis: the shard_map production
driver, exercised on 8 fake host devices.

Two execution shapes for one test body:

* when the process already has >= 8 devices (the CI lane exports
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before pytest
  starts) the checks run IN-PROCESS — this is the lane that actually
  exercises the shard_map path alongside the vmap lanes the rest of the
  suite uses;
* otherwise (the tier-1 run on a 1-device host) a subprocess sets the
  flag before jax initializes and runs the identical checks, mirroring
  ``tests/test_sharding.py``.

The checks: the shard_map driver conserves tasks, returns the FULL
``RebalanceStats`` (not just ``sizes_after``), matches the vmapped
driver lane-for-lane on both exchanges, honours an explicitly pinned
``ops=`` backend, and runs hierarchically over a pod axis.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

_HAVE_8 = jax.device_count() >= 8

_CHECKS = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.core import ops as bulk_ops
    from repro.core.master import RebalanceStats
    from repro.core.policy import StealPolicy
    from repro.core.sharded_queue import (make_sharded_queues,
                                          sharded_superstep,
                                          vmapped_superstep)

    SPEC = jax.ShapeDtypeStruct((), jnp.int32)
    OPS = bulk_ops.make_ops("reference")
    SIZES = [40, 0, 0, 0, 25, 0, 3, 0]

    def fill(qs, sizes):
        nxt = 1
        for i, n in enumerate(sizes):
            vals = np.zeros((max(sizes) + 1,), np.int32)
            vals[:n] = range(nxt, nxt + n)
            nxt += n
            qi = jax.tree_util.tree_map(lambda x: x[i], qs)
            qi, _ = OPS.push(qi, jnp.asarray(vals), n)
            qs = jax.tree_util.tree_map(
                lambda full, one: full.at[i].set(one), qs, qi)
        return qs

    def totals(qs):
        out = []
        for i in range(qs.size.shape[0]):
            qi = jax.tree_util.tree_map(lambda x: np.asarray(x)[i], qs)
            qi = bulk_ops.QueueState(
                buf=jax.tree_util.tree_map(jnp.asarray, qi.buf),
                lo=jnp.asarray(qi.lo), size=jnp.asarray(qi.size))
            while int(qi.size) > 0:
                qi, item, valid = OPS.pop(qi)
                assert bool(valid)
                out.append(int(item))
        return sorted(out)

    def seed():
        return fill(make_sharded_queues(8, 128, SPEC), SIZES)

    def run_checks():
        assert jax.device_count() >= 8, jax.device_count()
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))

        for exchange in ("compact", "dense"):
            pol = StealPolicy(proportion=0.5, low_watermark=2,
                              high_watermark=8, max_steal=32,
                              exchange=exchange)
            ids_before = totals(seed())
            qs = seed()
            qs_v = seed()
            step = sharded_superstep(mesh, pol)
            step_v = vmapped_superstep(pol)
            first = None
            for _ in range(3):
                qs, stats = step(qs)
                qs_v, stats_v = step_v(qs_v)
                first = first if first is not None else stats
            # full stats, not just sizes_after (round 1 surely steals)
            assert isinstance(stats, RebalanceStats), type(stats)
            assert int(np.asarray(first.n_steals)[0]) >= 1
            exp = 32 * 4 * (8 if exchange == "dense" else 1)
            assert int(np.asarray(first.bytes_moved)[0]) == exp
            assert int(np.asarray(stats.bytes_moved)[0]) in (0, exp)
            # shard_map == vmap, lane for lane (sizes AND stats)
            np.testing.assert_array_equal(np.asarray(qs.size),
                                          np.asarray(qs_v.size))
            np.testing.assert_array_equal(
                np.asarray(stats.sizes_after).reshape(-1),
                np.asarray(stats_v.sizes_after)[0])
            assert (int(np.asarray(stats.n_transferred)[0])
                    == int(np.asarray(stats_v.n_transferred)[0]))
            # conservation through the shard_map path
            assert totals(qs) == ids_before, exchange

        # explicit ops= pinning selects the same implementation
        pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                          max_steal=32)
        qs_a = seed()
        qs_b = seed()
        qs_a, _ = sharded_superstep(mesh, pol)(qs_a)
        qs_b, _ = sharded_superstep(mesh, pol, ops=OPS)(qs_b)
        np.testing.assert_array_equal(np.asarray(qs_a.size),
                                      np.asarray(qs_b.size))

        # hierarchical over a (2 pods x 4 workers) mesh
        mesh2 = jax.make_mesh(
            (2, 4), ("pods", "data"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ids_before = totals(seed())
        qs = seed()
        step_h = sharded_superstep(mesh2, pol, worker_axis="data",
                                   pod_axis="pods")
        for _ in range(3):
            qs, stats = step_h(qs)
        assert totals(qs) == ids_before
        assert int(np.asarray(qs.size).sum()) == sum(SIZES)
        # hierarchical stats expose the xpod fields (pod-level view)
        assert np.asarray(stats.n_steals_xpod).shape == (1,)
        assert np.asarray(stats.bytes_moved_xpod).shape == (1,)
        print("SHARDED-SUPERSTEP-OK")
""")


@pytest.mark.skipif(not _HAVE_8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8 before jax init (CI lane)")
def test_sharded_superstep_inprocess():
    ns = {}
    exec(compile(_CHECKS, "<sharded-superstep-checks>", "exec"), ns)
    ns["run_checks"]()


@pytest.mark.skipif(_HAVE_8, reason="in-process variant runs instead")
def test_sharded_superstep_subprocess():
    script = ('import os\n'
              'os.environ["XLA_FLAGS"] = '
              '"--xla_force_host_platform_device_count=8"\n'
              + _CHECKS + "\nrun_checks()\n")
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED-SUPERSTEP-OK" in out.stdout, out.stderr[-2000:]
