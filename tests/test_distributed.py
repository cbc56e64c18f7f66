"""`repro.distributed` on a real mesh axis: the FULL round loop under
shard_map, exercised on 8 fake host devices.

Same dual execution shape as ``tests/test_sharded_superstep.py``: with
>= 8 devices (the CI lane exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before pytest
starts) the checks run in-process; otherwise a subprocess sets the flag
before jax initializes and runs the identical checks.

The checks — the mesh executor is not "close to" the vmapped one, it is
bit-identical:

* ``MeshStealRuntime.run_fused`` (scan + early-exit while_loop) and
  ``round()`` produce bit-identical queues (buf/lo/size), RebalanceStats,
  telemetry ``RoundRecord`` streams (incl. ``bytes_moved``) and
  adaptive-proportion trajectories to ``StealRuntime`` — flat AND
  hierarchical (2x4 pod mesh), both exchanges, reference + auto
  backends;
* ``run_fused(k, until_drained=True)`` drains the Fig. 9 DAG workload
  (worker body with a collective) under shard_map, conserving the
  explored-node count and matching the vmapped drain round-for-round;
* ``launch_runtime`` selects both modes and validates its inputs;
* ``parallel_solve(execution="mesh")`` returns the DP optimum with the
  same superstep/exploration trajectory as the vmap path;
* ``RuntimeAdmissionMaster(execution="mesh")`` admits/rebalances request
  IDs on device lanes.
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

_HAVE_8 = jax.device_count() >= 8

_CHECKS = textwrap.dedent("""
    import dataclasses

    import jax, jax.numpy as jnp
    import numpy as np
    from jax import lax

    from repro.core.policy import StealPolicy
    from repro.distributed import (MeshStealRuntime, RuntimeAdmissionMaster,
                                   launch_runtime)
    from repro.launch.mesh import make_worker_mesh
    from repro.runtime import StealRuntime

    SPEC = jax.ShapeDtypeStruct((), jnp.int32)
    SIZES = [40, 0, 0, 0, 25, 0, 3, 0]

    def seed(rt):
        nxt = 1
        for i, n in enumerate(SIZES):
            if n:
                rt.push(i, jnp.arange(nxt, nxt + n, dtype=jnp.int32), n)
                nxt += n

    def assert_identical(vm, ms, stats_pairs=()):
        np.testing.assert_array_equal(np.asarray(vm.queues.size),
                                      np.asarray(ms.queues.size))
        np.testing.assert_array_equal(np.asarray(vm.queues.lo),
                                      np.asarray(ms.queues.lo))
        np.testing.assert_array_equal(np.asarray(vm.queues.buf),
                                      np.asarray(ms.queues.buf))
        for sv, sm in stats_pairs:
            for f in sv._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(sv, f)), np.asarray(getattr(sm, f)),
                    err_msg=f)
        assert vm.telemetry.rounds == ms.telemetry.rounds  # RoundRecords ==
        if vm.controller is not None:
            assert vm.controller.history == ms.controller.history

    def parity_checks():
        for pod_size in (None, 4):
            for backend in ("reference", "auto"):
                for exchange in ("compact", "dense"):
                    pol = StealPolicy(proportion=0.5, low_watermark=2,
                                      high_watermark=8, max_steal=32,
                                      exchange=exchange)
                    vm = launch_runtime(8, 128, SPEC, execution="vmap",
                                        pod_size=pod_size, policy=pol,
                                        backend=backend)
                    ms = launch_runtime(8, 128, SPEC, execution="mesh",
                                        pod_size=pod_size, policy=pol,
                                        backend=backend)
                    assert isinstance(ms, MeshStealRuntime)
                    assert ms.ops == vm.ops
                    seed(vm); seed(ms)
                    _, sv = vm.round()
                    _, sm = ms.round()
                    vm.run_fused(2)
                    ms.run_fused(2)
                    cv, _, rv = vm.run_fused(3, until_drained=True)
                    cm, _, rm = ms.run_fused(3, until_drained=True)
                    assert rv == rm
                    assert_identical(vm, ms, [(sv, sm)])
        print("PARITY-OK")

    N_NODES, BATCH, FANOUT = 3000, 16, 4

    def dag_body(ops):
        def body(q, carry):
            q, nodes, n_popped = ops.pop_bulk(q, BATCH, jnp.int32(BATCH))
            valid = jnp.arange(BATCH, dtype=jnp.int32) < n_popped
            kids = (nodes[:, None] * FANOUT + 1
                    + jnp.arange(FANOUT, dtype=jnp.int32)[None, :])
            live = valid[:, None] & (kids < N_NODES)
            flat, flive = kids.reshape(-1), live.reshape(-1)
            order = jnp.argsort(~flive, stable=True)
            flat = jnp.where(flive[order], flat[order], 0)
            q, _ = ops.push(q, flat, jnp.sum(flive.astype(jnp.int32)))
            # a worker-body collective, like the DD solver's incumbent
            peak = lax.pmax(carry, "workers")
            return q, carry + jnp.sum(valid.astype(jnp.int32)) + 0 * peak
        return body

    def dag_drain_checks():
        pol = StealPolicy(proportion=0.5, low_watermark=4,
                          high_watermark=32, max_steal=64)
        results = {}
        for mode in ("vmap", "mesh"):
            rt = launch_runtime(8, 1024, SPEC, execution=mode, policy=pol,
                                max_pop=BATCH)
            rt.push(0, jnp.zeros((1,), jnp.int32), 1)
            body = dag_body(rt.ops)
            carry = jnp.zeros((8,), jnp.int32)
            rounds = 0
            while rt.total_size() > 0 and rounds < 500:
                carry, _, r = rt.run_fused(16, body, carry,
                                           until_drained=True)
                rounds += r
            results[mode] = (int(jnp.sum(carry)), rounds,
                             rt.telemetry.rounds,
                             rt.controller.history)
        assert results["vmap"][0] == results["mesh"][0] == N_NODES
        assert results["vmap"][1] == results["mesh"][1]
        assert results["vmap"][2] == results["mesh"][2]
        assert results["vmap"][3] == results["mesh"][3]
        print("DAG-DRAIN-OK", results["mesh"][1])

    def launch_checks():
        try:
            launch_runtime(8, 64, SPEC, execution="threads")
        except ValueError as e:
            assert "execution" in str(e)
        else:
            raise AssertionError("bad execution accepted")
        try:
            launch_runtime(4, 64, SPEC, execution="mesh",
                           mesh=make_worker_mesh(8))
        except ValueError as e:
            assert "devices" in str(e)
        else:
            raise AssertionError("mismatched mesh accepted")
        try:
            make_worker_mesh(10_000)
        except ValueError as e:
            assert "devices" in str(e)
        else:
            raise AssertionError("oversized mesh accepted")
        try:  # a flat pinned mesh must not silently drop pod_size
            launch_runtime(8, 64, SPEC, execution="mesh",
                           mesh=make_worker_mesh(8), pod_size=4)
        except ValueError as e:
            assert "pod_size" in str(e)
        else:
            raise AssertionError("flat mesh + pod_size accepted")
        # pinned 2-axis mesh round-trips
        mesh = make_worker_mesh(8, pod_size=4)
        rt = launch_runtime(8, 64, SPEC, execution="mesh", mesh=mesh,
                            pod_size=4)
        assert rt.pod_size == 4 and rt.n_workers == 8
        # a host-level op on one lane runs on that lane's own device (a
        # Pallas queue kernel cannot be partitioned over the mesh)
        for w in (0, 5):
            leaf = rt.lane(w).size
            assert leaf.sharding.device_set == {mesh.devices.flat[w]}
        assert rt.push(5, jnp.arange(3, dtype=jnp.int32), 3) == 3
        assert [int(x) for x in rt.sizes()] == [0] * 5 + [3, 0, 0]
        print("LAUNCH-OK")

    def solver_checks():
        from repro.core.dd.knapsack import dp_solve, random_instance
        from repro.core.dd.parallel import parallel_solve

        inst = random_instance(10, seed=3)
        expect = dp_solve(inst)
        out = {}
        for mode in ("vmap", "mesh"):
            got, stats = parallel_solve(inst, n_workers=8, explore_width=8,
                                        batch=4, capacity=1024,
                                        execution=mode)
            assert got == expect, (mode, got, expect)
            assert stats["execution"] == mode
            out[mode] = stats
        # same optimum AND the same superstep trajectory
        assert out["vmap"]["supersteps"] == out["mesh"]["supersteps"]
        assert out["vmap"]["explored"] == out["mesh"]["explored"]
        assert (out["vmap"]["per_worker_explored"]
                == out["mesh"]["per_worker_explored"])
        print("SOLVER-OK", out["mesh"]["supersteps"])

    def serve_checks():
        from repro.serve.scheduler import Request

        master = RuntimeAdmissionMaster(8, execution="mesh", capacity=64)
        reqs = [Request(prompt=[1, 2, 3]) for _ in range(20)]
        # all 20 to one replica (bulk admission picks the least loaded
        # ONCE per submit call)
        master.submit(reqs)
        loads = [r.load() for r in master.replicas]
        assert sum(loads) == 20 and max(loads) == 20
        moved = master.rebalance_many(8)
        assert moved > 0
        loads = [r.load() for r in master.replicas]
        assert sum(loads) == 20 and max(loads) < 20
        wave = master.replicas[int(np.argmax(loads))].pop_wave(4)
        assert len(wave) == 4 and all(isinstance(r, Request) for r in wave)
        st = master.stats()
        assert st["execution"] == "mesh" and st["stolen"] == moved
        assert st["telemetry"]["rounds"] == master.rounds
        print("SERVE-OK")

    def decode_parity_checks():
        # The continuous-batching decode engine is bit-identical between
        # vmap lanes and the per-device mesh: same served tokens per
        # request AND the same admit/first/finish round stamps.
        from repro import configs
        from repro.models import build_model
        from repro.serve.decode import DecodeCluster, DecodePolicy
        from repro.serve.scheduler import Request

        cfg = configs.reduced(configs.get("llama3.2-1b"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        data = [(list(rng.integers(1, 100, size=int(rng.integers(1, 7)))),
                 int(rng.integers(1, 5))) for _ in range(10)]
        out = {}
        for mode in ("vmap", "mesh"):
            cl = DecodeCluster(
                model, params, n_lanes=8, capacity=32, execution=mode,
                policy=DecodePolicy(n_slots=2, max_prompt=8, max_new=4,
                                    page_size=4))
            reqs = [Request(prompt=p, max_new=mn) for p, mn in data]
            cl.submit(reqs[:6]); cl.step(); cl.submit(reqs[6:])
            done = cl.run_until_drained(max_steps=100)
            assert len(done) == len(data), (mode, len(done))
            # rid auto-increments globally across clusters; compare by
            # submission index
            idx = {r.rid: i for i, r in enumerate(reqs)}
            out[mode] = (
                sorted((idx[r.rid], tuple(r.output)) for r in done),
                sorted((idx[r.rid], r.admit, r.first, r.finish, r.tokens)
                       for r in cl.telemetry.requests))
        assert out["vmap"][0] == out["mesh"][0]   # served tokens
        assert out["vmap"][1] == out["mesh"][1]   # SLO round stamps
        print("DECODE-PARITY-OK")

    def run_checks():
        assert jax.device_count() >= 8, jax.device_count()
        parity_checks()
        dag_drain_checks()
        launch_checks()
        solver_checks()
        serve_checks()
        decode_parity_checks()
        print("DISTRIBUTED-OK")
""")


@pytest.mark.skipif(not _HAVE_8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8 before jax init (CI lane)")
def test_distributed_inprocess():
    ns = {}
    exec(compile(_CHECKS, "<distributed-checks>", "exec"), ns)
    ns["run_checks"]()


@pytest.mark.skipif(_HAVE_8, reason="in-process variant runs instead")
def test_distributed_subprocess():
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
    assert "DISTRIBUTED-OK" in out.stdout, out.stderr[-3000:]
