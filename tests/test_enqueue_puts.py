"""A steady run of fused dispatches hands the devices only the dispatch:
the proportion and the round index the previous block left on the
devices start the next one, and a worker body's closed-over constants
were placed once, where the program reads them.  No host-to-device put
and no cross-device copy is made after a runtime's first dispatch.

Where the host changes either value (a straggler boost, a restored state,
rounds run by ``round()`` or counted outside ``run_fused``) the dispatch
puts the host's value instead, and every result stays bit-identical to a
run that puts both values on every dispatch.

Each case runs on the vmapped runtime or on ``MeshStealRuntime`` over four
devices.  The mesh cases need four devices before JAX starts, so on a
one-device process they run together in one subprocess that sets them
up."""

import contextlib
import itertools
import json
import os
import re
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import StealPolicy
from repro.distributed import launch_runtime
from repro.obs import spans
from repro.runtime import FaultPlan
from repro.runtime.executor import LiftedJit

W, K, N_NODES, BATCH, FANOUT = 4, 4, 2000, 8, 2
DISPATCHES = 6
CHANGE_AT = 2  # the host change comes before this dispatch
POLICY = StealPolicy(queue_limit=2, low_watermark=1, high_watermark=8,
                     max_steal=64)
PLAN = FaultPlan(kills=((3, 5),), delays=((1, 2, 2),), drops=(7,))


def _body(ops):
    """The DAG drain's shape of worker body: it closes over array
    constants (a fanout, a row of child offsets, two uint32 hash words),
    which the traced program takes as arguments."""
    fan = jnp.int32(FANOUT)
    offsets = jnp.arange(FANOUT, dtype=jnp.int32)[None, :]
    salt = jnp.uint32(7)
    mul = jnp.uint32(0x9E3779B1)

    def body(q, carry):
        q, nodes, n = ops.pop_bulk(q, BATCH, jnp.int32(BATCH))
        valid = jnp.arange(BATCH, dtype=jnp.int32) < n
        kids = nodes[:, None] * fan + 1 + offsets
        live = (valid[:, None] & (kids < N_NODES)).reshape(-1)
        kids = kids.reshape(-1)
        order = jnp.argsort(~live, stable=True)
        q, _ = ops.push(q, jnp.where(live[order], kids[order], 0),
                        jnp.sum(live.astype(jnp.int32)))
        h = (nodes.astype(jnp.uint32) ^ salt) * mul
        return q, {"explored": carry["explored"] + n,
                   "hash_sum": carry["hash_sum"] + jnp.sum(
                       jnp.where(valid, h, jnp.uint32(0)))}

    return body


def _make(execution, pod_size=None, **kwargs):
    """A seeded runtime, its body and a carry placed as the block leaves
    its own (lane-sharded on the mesh), so the first dispatch and the
    later ones take one argument signature."""
    rt = launch_runtime(W, 1024, jax.ShapeDtypeStruct((), jnp.int32),
                        execution=execution, pod_size=pod_size,
                        policy=kwargs.pop("policy", POLICY), max_pop=BATCH,
                        **kwargs)
    rt.push(0, jnp.zeros((1,), jnp.int32), 1)
    carry = {"explored": jnp.zeros((W,), jnp.int32),
             "hash_sum": jnp.zeros((W,), jnp.uint32)}
    if execution == "mesh":
        carry = jax.device_put(carry, rt.sharding)
    return rt, _body(rt.ops), carry


@contextlib.contextmanager
def _recording():
    """A profiler session, the switch that turns the counters on."""
    spans.reset()
    with tempfile.TemporaryDirectory() as path:
        jax.profiler.start_trace(path)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            spans.reset()


def _puts() -> int:
    return spans.snapshot()["counters"].get("steal.enqueue_puts", 0)


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _assert_same_leaves(a, b):
    la, ta = jax.tree_util.tree_flatten(_host(a))
    lb, tb = jax.tree_util.tree_flatten(_host(b))
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


# -- no transfer after the first dispatch -------------------------------------

GUARD_CASES = [dict(execution=e, until_drained=d, pod_size=p)
               for e, d, p in itertools.product(("vmap", "mesh"),
                                                (True, False), (None, 2))]


def guard_id(case):
    return "-".join([case["execution"],
                     "drained" if case["until_drained"] else "scan",
                     "flat" if case["pod_size"] is None else "pods"])


def check_no_transfer(case):
    rt, body, carry = _make(case["execution"], case["pod_size"])
    drained = case["until_drained"]
    carry = rt.run_fused(K, body, carry, until_drained=drained)[0]
    program = rt._compiled[("fused", body, K, drained)]
    executables = program._cache_size()
    for _ in range(DISPATCHES - 1):
        with jax.transfer_guard_host_to_device("disallow_explicit"), \
                jax.transfer_guard_device_to_device("disallow_explicit"):
            carry = rt.run_fused(K, body, carry, until_drained=drained)[0]
    # The first dispatch's fresh values and the later held ones run one
    # executable.
    assert program._cache_size() == executables == 1
    assert rt.rounds_run == DISPATCHES * K


# -- falling back to a put where the host changed a value ---------------------

def _boost(rt, body, carry, saved):
    rt.note_straggler(rounds=2, factor=1.5)
    return carry


def _load_state(rt, body, carry, saved):
    rt.load_state_dict(saved)
    return carry


def _round(rt, body, carry, saved):
    return rt.round(body, carry)[0]


def _bump_rounds(rt, body, carry, saved):
    rt.rounds_run += K  # as a patched run_fused that runs nothing does
    return carry


def _nothing(rt, body, carry, saved):
    return carry


# name -> (runtime keywords, the host change, puts on the dispatch after
# it: an exact count, or None for "at least one").
CHANGES = {
    "fixed-proportion": (dict(adaptive=False,
                              policy=StealPolicy(
                                  proportion=0.3, queue_limit=2,
                                  low_watermark=1, high_watermark=8,
                                  max_steal=64)), _nothing, 0),
    "boost": ({}, _boost, 1),
    "load-state-dict": ({}, _load_state, None),
    "round": ({}, _round, None),
    "rounds-bumped": ({}, _bump_rounds, 1),
    "fault-plan": (dict(fault_plan=PLAN), _nothing, 1),
}
FALLBACK_CASES = [dict(execution=e, change=c)
                  for e, c in itertools.product(("vmap", "mesh"), CHANGES)]


def fallback_id(case):
    return f"{case['execution']}-{case['change']}"


def _drive(case, fresh: bool):
    """Scan dispatches with the case's host change before dispatch
    CHANGE_AT.  ``fresh`` drops what the last dispatch left on the
    devices before each one, so every dispatch puts both values.
    Returns the runtime, the carry, each dispatch's stats and its puts."""
    kwargs, change, _ = CHANGES[case["change"]]
    rt, body, carry = _make(case["execution"], **dict(kwargs))
    stats, puts, saved = [], [], None
    with _recording():
        for i in range(DISPATCHES):
            if i == CHANGE_AT - 1:
                saved = _host(rt.state_dict())
            if i == CHANGE_AT:
                carry = change(rt, body, carry, saved)
            if fresh:
                rt._held_p = rt._held_ctx = (None, None)
            before = _puts()
            carry, s = rt.run_fused(K, body, carry)
            puts.append(_puts() - before)
            stats.append(_host(s))
            # What the dispatch left on the devices is what it stands for.
            bits, p = rt._held_p
            assert np.float32(p).view(np.uint32) == bits
            held_round, ctx = rt._held_ctx
            assert held_round == rt.rounds_run
            if rt.fault is None:
                assert int(ctx) == held_round
    return rt, carry, stats, puts


def check_fallback(case):
    rt, carry, stats, puts = _drive(case, fresh=False)
    ref, ref_carry, ref_stats, _ = _drive(case, fresh=True)
    for a, b in zip(stats, ref_stats):
        _assert_same_leaves(a, b)
    assert rt.rounds_run == ref.rounds_run
    assert rt.telemetry.rounds == ref.telemetry.rounds
    if rt.controller is not None:
        assert rt.controller.history == ref.controller.history
    _assert_same_leaves((rt.queues, carry), (ref.queues, ref_carry))

    steady = 1 if rt.fault is not None else 0  # the schedule is put
    expected = CHANGES[case["change"]][2]
    assert puts[0] == 2
    if expected is None:
        assert puts[CHANGE_AT] >= 1
    else:
        assert puts[CHANGE_AT] == expected
    others = [n for i, n in enumerate(puts) if i not in (0, CHANGE_AT)]
    assert others == [steady] * len(others)


# -- the placed constants leave the program as it was -------------------------

def check_same_program(case):
    """The fused block with its constants placed compiles to the program
    it compiled to with the constants left where tracing made them; only
    the module header's per-parameter propagation flags may differ."""
    rt, body, carry = _make(case["execution"])
    placed = rt._compile_fused(body, K, True)
    plain = LiftedJit(placed._fn, ())
    args = (rt.queues, carry, *rt._fused_scalars(0))

    def text(program):
        lines = program.lower(*args).compile().as_text().splitlines()
        return [re.sub(r"jit__eval\S*", "", ln) for ln in lines[1:]]

    assert text(placed) == text(plain)
    consts = next(iter(placed._traced.values()))[1]
    assert consts and all(
        c.sharding.is_fully_replicated for c in consts)


# -- running the cases ---------------------------------------------------------

def mesh_checks():
    """Every mesh case of this module: ``(name, check, case)``."""
    return ([(guard_id(c), check_no_transfer, c)
             for c in GUARD_CASES if c["execution"] == "mesh"]
            + [(fallback_id(c), check_fallback, c)
               for c in FALLBACK_CASES if c["execution"] == "mesh"]
            + [("mesh-same-program", check_same_program,
                dict(execution="mesh"))])


_MESH_RESULTS = """
import json, sys, traceback
sys.path.insert(0, {tests!r})
import test_enqueue_puts as t
out = {{}}
for name, check, case in t.mesh_checks():
    try:
        check(case)
        out[name] = "ok"
    except Exception:
        out[name] = traceback.format_exc()[-3000:]
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


def _run(check, case, name, request):
    if case["execution"] == "vmap" or jax.device_count() >= W:
        check(case)
    else:
        result = request.getfixturevalue("mesh_results")[name]
        assert result == "ok", result


@pytest.mark.parametrize("case", GUARD_CASES, ids=guard_id)
def test_steady_dispatches_transfer_nothing(case, request):
    _run(check_no_transfer, case, guard_id(case), request)


@pytest.mark.parametrize("case", FALLBACK_CASES, ids=fallback_id)
def test_host_changes_fall_back_to_a_put(case, request):
    _run(check_fallback, case, fallback_id(case), request)


def test_placed_constants_leave_the_program_as_it_was(request):
    _run(check_same_program, dict(execution="mesh"), "mesh-same-program",
         request)
