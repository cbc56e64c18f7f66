"""A fused dispatch reads its results back in one packed buffer, and what
it returns and records is bit-identical to the same rounds driven one at
a time through ``round()``: the stats, the executed round counts, every
``RoundRecord``, the telemetry summary, the controller's float32
proportion history, the queues and the carry.

Each case runs flat or hierarchical (two pods of two), with or without a
``FaultPlan``, early-exit or fixed-length blocks, on the vmapped runtime
or on ``MeshStealRuntime`` over four devices.  The mesh cases need four
devices before JAX starts, so on a one-device process they run together
in one subprocess that sets them up."""

import itertools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import StealPolicy
from repro.distributed import launch_runtime
from repro.runtime import FaultPlan

W, K, N_NODES, BATCH = 4, 4, 600, 8
SCAN_BLOCKS = 3
MAX_DISPATCHES = 100
POLICY = StealPolicy(queue_limit=2, low_watermark=1, high_watermark=8,
                     max_steal=64)
# Lane 3 dies at round 5, lane 1 skips its body in rounds 2-3 and round
# 7's exchange is dropped.
PLAN = FaultPlan(kills=((3, 5),), delays=((1, 2, 2),), drops=(7,))

CASES = [dict(execution=e, until_drained=d, pod_size=p, fault=f)
         for e, d, p, f in itertools.product(("vmap", "mesh"),
                                             (True, False), (None, 2),
                                             (False, True))]


def case_id(case):
    return "-".join([case["execution"],
                     "drained" if case["until_drained"] else "scan",
                     "flat" if case["pod_size"] is None else "pods",
                     "fault" if case["fault"] else "nofault"])


def _body(ops):
    """Binary-tree expansion: pop up to BATCH nodes, push their children."""

    def body(q, carry):
        q, nodes, n = ops.pop_bulk(q, BATCH, jnp.int32(BATCH))
        valid = jnp.arange(BATCH) < n
        kids = (nodes[:, None] * 2 + jnp.arange(1, 3)[None, :]).reshape(-1)
        live = jnp.repeat(valid, 2) & (kids < N_NODES)
        order = jnp.argsort(~live, stable=True)
        q, _ = ops.push(q, jnp.where(live[order], kids[order], 0),
                        jnp.sum(live.astype(jnp.int32)))
        return q, carry + n

    return body


def _make(case):
    rt = launch_runtime(W, 256, jax.ShapeDtypeStruct((), jnp.int32),
                        execution=case["execution"],
                        pod_size=case["pod_size"], policy=POLICY,
                        max_pop=BATCH,
                        fault_plan=PLAN if case["fault"] else None)
    rt.push(0, jnp.zeros((1,), jnp.int32), 1)
    return rt, _body(rt.ops), jnp.zeros((W,), jnp.int32)


def _fused(case):
    """Dispatches of K-round blocks: until drained, or SCAN_BLOCKS
    fixed-length ones.  Returns the runtime, the carry and each
    dispatch's ``(rounds, stats)``."""
    rt, body, carry = _make(case)
    out = []
    for _ in range(MAX_DISPATCHES if case["until_drained"] else SCAN_BLOCKS):
        if case["until_drained"]:
            carry, stats, rounds = rt.run_fused(K, body, carry,
                                                until_drained=True)
            if rounds == 0:
                break
        else:
            carry, stats = rt.run_fused(K, body, carry)
            rounds = K
        out.append((rounds, stats))
    return rt, carry, out


def _rounds(case, blocks):
    """The same rounds one ``round()`` at a time, grouped as the fused
    dispatches grouped them: up to K rounds while work is left (the early
    exit's test, made before each round), or exactly K."""
    rt, body, carry = _make(case)
    out = []
    for _ in range(blocks):
        per_round = []
        while len(per_round) < K and (not case["until_drained"]
                                      or rt.total_size() > 0):
            carry, stats = rt.round(body, carry)
            per_round.append(jax.tree_util.tree_map(np.asarray, stats))
        out.append((len(per_round), jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *per_round)))
    return rt, carry, out


def _assert_same_leaves(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert isinstance(x, np.ndarray)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def check_case(case):
    f_rt, f_carry, f_out = _fused(case)
    if case["until_drained"]:
        assert f_rt.total_size() == 0
        assert int(np.sum(np.asarray(f_carry))) == N_NODES
    r_rt, r_carry, r_out = _rounds(case, len(f_out))
    assert [n for n, _ in f_out] == [n for n, _ in r_out]
    for (_, fs), (_, rs) in zip(f_out, r_out):
        _assert_same_leaves(fs, rs)
    assert f_rt.rounds_run == r_rt.rounds_run
    assert f_rt.telemetry.rounds == r_rt.telemetry.rounds
    assert f_rt.telemetry.summary() == r_rt.telemetry.summary()
    # Every proportion, float32-exact, and the one after the last round.
    assert f_rt.controller.history == r_rt.controller.history
    assert len(f_rt.controller.history) == f_rt.rounds_run + 1
    _assert_same_leaves(
        jax.tree_util.tree_map(np.asarray, (f_rt.queues, f_carry)),
        jax.tree_util.tree_map(np.asarray, (r_rt.queues, r_carry)))


_MESH_RESULTS = """
import json, sys, traceback
sys.path.insert(0, {tests!r})
import test_fused_readback as t
out = {{}}
for case in t.CASES:
    if case["execution"] == "mesh":
        try:
            t.check_case(case)
            out[t.case_id(case)] = "ok"
        except Exception:
            out[t.case_id(case)] = traceback.format_exc()[-3000:]
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_results():
    """Each mesh case's outcome, run in one subprocess with four CPU
    devices: ``"ok"`` or the failure's traceback."""
    tests = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(tests, "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_RESULTS.format(tests=tests)], env=env,
        capture_output=True, text=True, timeout=900)
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS ")]
    assert line, proc.stderr[-3000:]
    return json.loads(line[0][len("RESULTS "):])


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_run_fused_equals_single_rounds(case, request):
    if case["execution"] == "vmap" or jax.device_count() >= W:
        check_case(case)
    else:
        result = request.getfixturevalue("mesh_results")[case_id(case)]
        assert result == "ok", result
