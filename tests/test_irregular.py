"""The Fig. 9 DAG with heavy-tailed per-node cost (``repro.core.irregular``)
and the runtime's contract for work in flight.

The cost table is the Bounded Pareto law's float64 CDF; the worker
body's cost draws and SHA-1 chains are bit-exact against ``hashlib``; a
drain through ``launch_runtime`` -> ``run_fused`` equals the sequential
``hashlib`` walk, vmapped and on four devices; and a drain whose lanes
hold nodes with empty queues does not end early, in the early-exit
block (vmap and mesh) and in ``run(fused=1)``.  A carry without the
``in_flight`` leaf keeps the queue-only exit test.  The mesh cases need
four devices before JAX starts, so they run together in one subprocess
that sets them up."""

import hashlib
import json
import math
import os
import re
import subprocess
import sys
import warnings

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import irregular
from repro.core.irregular import IrregularDrain, ParetoCost
from repro.core.ops import (BackendFallbackWarning, make_ops, make_queue,
                            reset_fallback_warnings)
from repro.core.policy import StealPolicy
from repro.runtime.executor import IN_FLIGHT

W, SLOTS, RING, N_NODES = 4, 16, 256, 2000
SMALL = ParetoCost(alpha=1.1, k=1, p=64)
CELL = ParetoCost(alpha=1.1, k=1, p=1024)
# fig9-dag's policy, which the cell runs.
PAPER = StealPolicy(proportion=0.5, queue_limit=2, low_watermark=1,
                    high_watermark=8, max_steal=8192)
SALT = 0xC0FFEE
ROOT_COST = 50


def _bytes(words) -> bytes:
    return np.asarray(words, np.uint32).astype(">u4").tobytes()


def _chain(v: int, cost: ParetoCost):
    """Node ``v``'s cost and final digest, by hashlib and the law's CDF
    in float64."""
    digest = hashlib.sha1(bytes(16) + v.to_bytes(4, "big")).digest()
    u = (int.from_bytes(digest[16:], "big") & 0x7FFFFFFF) / 2.0**31
    c = cost.k
    while c + 1 < cost.p and u >= cost.cdf(c + 1):
        c += 1
    for i in range(1, c):
        digest = hashlib.sha1(digest + i.to_bytes(4, "big")).digest()
    return c, digest


class RootCost(ParetoCost):
    """A table that gives node 0 exactly ``ROOT_COST`` units: the
    thresholds below its draw, then the rest above it."""

    def table(self):
        root = hashlib.sha1(bytes(20)).digest()
        d = int.from_bytes(root[16:], "big") & 0x7FFFFFFF
        below = ROOT_COST - self.k
        return np.array([0] * below + [d + 1] * (self.p - 1 - self.k - below),
                        np.uint32)


ONE_NODE = RootCost(1.1, 1, 64)


# ---------------------------------------------------------------------------
# The law
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cost", [SMALL, CELL], ids=["p64", "p1024"])
def test_threshold_table_matches_the_float64_cdf(cost):
    table = cost.table().astype(np.int64)
    assert table.shape == (cost.p - 1 - cost.k,)
    assert (np.diff(table) >= 0).all() and table[-1] < 2**31
    for j, t in zip(range(cost.k, cost.p - 1), table):
        f = (1.0 - (cost.k / (j + 1)) ** cost.alpha) / (
            1.0 - (cost.k / cost.p) ** cost.alpha)
        assert t == math.ceil(2**31 * f)


def test_cell_law_gives_the_published_moments():
    """The issue's figures for the cell: mean 5.087, 53.4% of nodes at
    cost 1, about 1,397 nodes a drain at cost >= 512."""
    edges = np.concatenate([[0], CELL.table(), [2**31]]).astype(np.float64)
    probs = np.diff(edges) / 2**31
    assert (probs * np.arange(1, 1024)).sum() == pytest.approx(5.0872,
                                                               abs=1e-4)
    assert probs[0] == pytest.approx(0.5337, abs=1e-4)
    assert 2_500_000 * probs[511:].sum() == pytest.approx(1396.6, abs=0.1)


def test_device_costs_and_chains_equal_hashlib():
    """10,000 ids through the worker body on one lane of 10,000 slots:
    each slot's cost (``step`` when its node retires) and final digest
    equal ``hashlib``'s chain of that id."""
    n = 10_000
    ops = make_ops("reference")
    ids = np.arange(5_000_000, 5_000_000 + n, dtype=np.int32)
    q, _ = ops.push(make_queue(16_384, irregular.ITEM), jnp.asarray(ids), n)
    # One node in the tree: every popped id is a leaf, so nothing is
    # pushed back and each slot keeps its last node's digest.
    body = jax.jit(irregular.make_body(1, 4, SMALL, n, SALT, ops))
    carry = {k: v[0] for k, v in irregular.zero_carry(1, n).items()}
    first = None
    for _ in range(SMALL.p):
        q, carry = body(q, carry)
        if first is None:
            first = np.asarray(carry["id"])
        if int(carry[IN_FLIGHT]) == 0:
            break
    assert int(carry[IN_FLIGHT]) == 0 and int(q.size) == 0
    slot_ids = np.asarray(carry["id"])
    assert (slot_ids == first).all() and sorted(slot_ids) == list(ids)
    steps, digests = np.asarray(carry["step"]), np.asarray(carry["digest"])
    want_units = 0
    for slot in range(n):
        c, want = _chain(int(slot_ids[slot]), SMALL)
        assert steps[slot] == c, slot_ids[slot]
        assert _bytes(digests[slot]) == want, slot_ids[slot]
        want_units += c
    assert int(carry["units"]) == want_units == int(carry["busy_slot_rounds"])
    assert int(carry["explored"]) == n


def test_reference_drain_walks_the_tree_with_hashlib():
    ref = irregular.reference_drain(N_NODES, 4, SMALL, SALT)
    chains = [_chain(v, SMALL) for v in range(N_NODES)]
    assert ref["nodes"] == N_NODES
    assert ref["id_sum"] == N_NODES * (N_NODES - 1) // 2
    assert ref["units"] == sum(c for c, _ in chains)
    words = np.frombuffer(b"".join(d for _, d in chains), ">u4").reshape(
        -1, 5)
    x = np.full(N_NODES, SALT, np.uint64)
    for k in range(5):
        x = ((x ^ words[:, k]) * np.uint64(0x9E3779B1)) & np.uint64(
            0xFFFFFFFF)
        x ^= x >> np.uint64(15)
    assert ref["hash_sum"] == int(x.sum()) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Drains through the runtime, vmapped and on the mesh
# ---------------------------------------------------------------------------


def _drain(execution: str, n_nodes=N_NODES, cost=SMALL):
    return IrregularDrain(n_nodes, W, fanout=4, cost=cost, slots=SLOTS,
                          ring_slots=RING, policy=PAPER, backend="reference",
                          fused_rounds=8, salt=SALT, execution=execution)


def check_drain_equals_reference(execution: str) -> None:
    drain = _drain(execution)
    got = drain.run(1)
    ref = irregular.reference_drain(N_NODES, 4, SMALL, SALT)
    for key in ("nodes", "id_sum", "units", "hash_sum"):
        assert got[key] == ref[key], key
    assert got["busy_slot_rounds"] == ref["units"]
    assert got["final_sizes"] == [0] * W and got["in_flight"] == 0
    # Nodes stayed in flight over rounds that began with empty queues,
    # and the master moved some between lanes.
    records = drain.rt.telemetry.rounds
    assert any(r.sizes_total == 0 for r in records[:-1])
    assert sum(r.n_transferred for r in records) > 0


def _final_hash(v: int, cost: int, salt: int) -> int:
    """The checksum term of node ``v`` run for ``cost`` units."""
    digest = hashlib.sha1(bytes(16) + v.to_bytes(4, "big")).digest()
    for i in range(1, cost):
        digest = hashlib.sha1(digest + i.to_bytes(4, "big")).digest()
    x = salt
    for k in range(5):
        x = ((x ^ int.from_bytes(digest[4 * k:4 * k + 4], "big"))
             * 0x9E3779B1) & 0xFFFFFFFF
        x ^= x >> 15
    return x


def check_one_node_runs_its_cost(execution: str) -> None:
    """A one-node drain whose root costs 50 runs exactly 50 rounds: its
    queues are empty from the first round on, so the queue-only test
    would end it after one."""
    drain = _drain(execution, n_nodes=1, cost=ONE_NODE)
    got = drain.run(2)
    assert got["rounds"] == ROOT_COST
    assert (got["nodes"], got["units"], got["in_flight"]) == (1, ROOT_COST, 0)
    assert got["hash_sum"] == _final_hash(0, ROOT_COST, SALT)


def mesh_checks():
    return [("mesh-drain-equals-reference", check_drain_equals_reference),
            ("mesh-one-node-runs-its-cost", check_one_node_runs_its_cost)]


_MESH_RESULTS = """
import json, sys, traceback
sys.path.insert(0, {tests!r})
import test_irregular as t
out = {{}}
for name, check in t.mesh_checks():
    try:
        check("mesh")
        out[name] = "ok"
    except Exception:
        out[name] = traceback.format_exc()[-3000:]
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_results():
    """Each mesh check's outcome, run in one subprocess with four CPU
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


def _run(check, execution, request):
    if execution == "vmap" or jax.device_count() >= W:
        check(execution)
    else:
        name = f"mesh-{check.__name__[len('check_'):].replace('_', '-')}"
        result = request.getfixturevalue("mesh_results")[name]
        assert result == "ok", result


@pytest.mark.parametrize("execution", ["vmap", "mesh"])
def test_drain_equals_the_reference(execution, request):
    _run(check_drain_equals_reference, execution, request)


@pytest.mark.parametrize("execution", ["vmap", "mesh"])
def test_work_in_flight_keeps_the_drain_running(execution, request):
    _run(check_one_node_runs_its_cost, execution, request)


# ---------------------------------------------------------------------------
# StealRuntime.run and the carry without in_flight
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [1, 8])
def test_run_honours_in_flight(fused):
    drain = _drain("vmap", n_nodes=1, cost=ONE_NODE)
    drain.seed(0)
    carry = drain.rt.run(drain.body, drain.zero_carry(), fused=fused)
    assert drain.rt.rounds_run == ROOT_COST
    assert int(np.asarray(carry["units"]).sum()) == ROOT_COST
    assert int(np.asarray(carry[IN_FLIGHT]).sum()) == 0


def _hide_in_flight(body):
    """The slot-pool body with its carry's ``in_flight`` leaf renamed, as
    a body that does not report its work would carry it."""

    def hidden(q, carry):
        carry = dict(carry, in_flight=carry.pop("held"))
        q, carry = body(q, carry)
        carry["held"] = carry.pop(IN_FLIGHT)
        return q, carry

    return hidden


def _hidden_carry(drain):
    carry = drain.zero_carry()
    carry["held"] = carry.pop(IN_FLIGHT)
    return carry


@pytest.mark.parametrize("fused", [1, 8])
def test_carry_without_in_flight_keeps_the_queue_test(fused):
    """Without the leaf, the exit test is the queues alone: the one-node
    drain stops after its first round, with its root still held."""
    drain = _drain("vmap", n_nodes=1, cost=ONE_NODE)
    drain.seed(0)
    body = _hide_in_flight(drain.body)
    carry = drain.rt.run(body, _hidden_carry(drain), fused=fused)
    assert drain.rt.rounds_run == 1
    assert int(np.asarray(carry["held"]).sum()) == 1
    assert int(np.asarray(carry["explored"]).sum()) == 0


def _cond_reads(rt, body, carry) -> set:
    """Which leaves of ``(queues, carry)`` the early-exit block's loop
    condition reads."""
    fn = rt._compile_fused(body, 8, True)
    args = (rt.queues, carry, jnp.float32(0.5), jnp.int32(0))
    closed = jax.make_jaxpr(fn._fn)(*args)
    (loop,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "while"]
    cond = loop.params["cond_jaxpr"].jaxpr
    n_consts = loop.params["cond_nconsts"]
    used = {v for e in cond.eqns for v in e.invars
            if isinstance(v, jax.extend.core.Var)}
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path((rt.queues, carry))[0]]
    state = cond.invars[n_consts:]
    return {paths[i] for i, v in enumerate(state[:len(paths)]) if v in used}


def test_exit_test_reads_in_flight_only_where_the_carry_has_it():
    drain = _drain("vmap", n_nodes=1, cost=ONE_NODE)
    with_leaf = _cond_reads(drain.rt, drain.body, drain.zero_carry())
    without = _cond_reads(drain.rt, _hide_in_flight(drain.body),
                          _hidden_carry(drain))
    assert without == {"[0].size"}
    assert with_leaf == {"[0].size", "[1]['in_flight']"}


# ---------------------------------------------------------------------------
# The cell's geometry
# ---------------------------------------------------------------------------


def test_cell_geometry_runs_every_queue_op_on_the_kernels():
    """At the cell's geometry (16,384-slot rings, pops of 512, pushes of
    2,048, max_steal 8,192) ``auto`` routes every op to the kernels and
    no call falls back."""
    reset_fallback_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("error", BackendFallbackWarning)
        drain = IrregularDrain(2_500_000, 8, cost=CELL, slots=512,
                               ring_slots=16_384, policy=PAPER,
                               backend="auto")
        assert drain.rt.ops.resolved == "pallas"
        drain.seed(5)
        drain.rt.lower(drain.body, drain.zero_carry(), fused=8)
    assert drain.rt.sizes().tolist() == [0] * 5 + [1, 0, 0]


def test_phases_are_named_in_the_compiled_block():
    drain = _drain("vmap")
    text = drain.rt.lower(drain.body, drain.zero_carry(),
                          fused=8).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("irregular.refill", "irregular.work", "irregular.retire"):
        assert any(scope in name for name in names), scope
