"""Host spans and counters (``repro.obs.spans``): recording follows the
profiler, changes no result and no program, tiles the fused dispatch,
counts compiles and solves, shares the profiler's clock, and the named
scopes of the round reach the compiled program's metadata."""

import contextlib
import glob
import json
import os
import subprocess
import sys
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dd import parallel
from repro.core.dd.knapsack import dp_solve, random_instance
from repro.core.dd.parallel import parallel_solve
from repro.core.policy import StealPolicy
from repro.distributed.elastic import compile_count
from repro.obs import spans
from repro.runtime import FaultPlan, StealRuntime
from repro.runtime.executor import LiftedJit

N_NODES = 600
CHILDREN = ("steal:enqueue", "steal:wait", "steal:readback", "steal:record")
SCOPES = ("worker_body", "master.sizes", "master.plan", "master.exchange",
          "master.splice", "adaptive", "queue.pop", "queue.push",
          "queue.window", "queue.transfer")


@pytest.fixture(autouse=True)
def _clean():
    spans.reset()
    yield
    spans.reset()


@contextlib.contextmanager
def recording(path):
    """A profiler session, the switch that turns recording on."""
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _make_rt():
    return StealRuntime(4, 256, jax.ShapeDtypeStruct((), jnp.int32),
                        max_pop=8,
                        policy=StealPolicy(queue_limit=2, low_watermark=1,
                                           high_watermark=8, max_steal=64))


def _body(ops):
    """Binary-tree expansion: pop up to 8 nodes, push their children."""

    def body(q, carry):
        q, nodes, n = ops.pop_bulk(q, 8, jnp.int32(8))
        valid = jnp.arange(8) < n
        kids = (nodes[:, None] * 2 + jnp.arange(1, 3)[None, :]).reshape(-1)
        live = jnp.repeat(valid, 2) & (kids < N_NODES)
        order = jnp.argsort(~live, stable=True)
        q, _ = ops.push(q, jnp.where(live[order], kids[order], 0),
                        jnp.sum(live.astype(jnp.int32)))
        return q, carry + n

    return body


def _drive(rt, mode):
    """Seed the root, then drain it by ``mode``: early-exit fused blocks,
    fixed-length fused blocks, or single rounds."""
    rt.push(0, jnp.zeros((1,), jnp.int32), 1)
    body = _body(rt.ops)
    carry = jnp.zeros((rt.n_workers,), jnp.int32)
    dispatches = 0
    while rt.total_size() > 0:
        if mode == "drained":
            carry, _, _ = rt.run_fused(4, body, carry, until_drained=True)
        elif mode == "scan":
            carry, _ = rt.run_fused(4, body, carry)
        else:
            carry, _ = rt.round(body, carry)
        dispatches += 1
    return carry, dispatches


def _state(rt, carry):
    return [np.asarray(x)
            for x in jax.tree_util.tree_leaves((rt.queues, carry))]


@pytest.mark.parametrize("mode", ["drained", "scan", "round"])
def test_recording_changes_no_result_and_no_program(mode, tmp_path):
    off = _make_rt()
    off_carry, _ = _drive(off, mode)
    assert spans.snapshot() == {"spans": {}, "counters": {}}
    assert spans.recorded() == []

    on = _make_rt()
    with recording(tmp_path):
        on_carry, _ = _drive(on, mode)
    assert spans.snapshot()["counters"]["steal.rounds"] > 0

    assert int(np.sum(off_carry)) == N_NODES
    for a, b in zip(_state(off, off_carry), _state(on, on_carry)):
        np.testing.assert_array_equal(a, b)
    assert off.telemetry.summary() == on.telemetry.summary()
    assert compile_count(on) == compile_count(off)


@pytest.mark.parametrize("mode,parent", [("drained", "steal:run_fused"),
                                         ("scan", "steal:run_fused"),
                                         ("round", "steal:round")])
def test_children_tile_the_dispatch(mode, parent, tmp_path):
    rt = _make_rt()
    with recording(tmp_path):
        _, dispatches = _drive(rt, mode)
    snap = spans.snapshot()
    whole = snap["spans"][parent]
    children = sum(snap["spans"][name]["total_s"] for name in CHILDREN)
    assert whole["n"] == dispatches
    assert children >= 0.95 * whole["total_s"]
    assert whole["self_s"] == pytest.approx(whole["total_s"] - children,
                                            abs=1e-9)
    assert snap["counters"]["steal.dispatches"] == dispatches
    assert snap["counters"]["steal.rounds"] == len(rt.telemetry.rounds)
    assert snap["counters"]["steal.rounds"] == rt.rounds_run


@pytest.mark.parametrize("mode", ["drained", "scan"])
def test_one_host_read_per_fused_dispatch(mode, tmp_path):
    """``steal.host_reads`` counts the blocking device-to-host reads of
    ``run_fused``: one packed buffer a dispatch."""
    rt = _make_rt()
    with recording(tmp_path):
        _, dispatches = _drive(rt, mode)
    counters = spans.snapshot()["counters"]
    assert counters["steal.host_reads"] == counters["steal.dispatches"]
    assert counters["steal.host_reads"] == dispatches


def _puts_per_dispatch(execution, mode, path):
    """``steal.enqueue_puts`` added by each fused dispatch of a drain."""
    from repro.distributed import launch_runtime

    rt = launch_runtime(4, 256, jax.ShapeDtypeStruct((), jnp.int32),
                        execution=execution, max_pop=8,
                        policy=StealPolicy(queue_limit=2, low_watermark=1,
                                           high_watermark=8, max_steal=64))
    rt.push(0, jnp.zeros((1,), jnp.int32), 1)
    body = _body(rt.ops)
    carry = jnp.zeros((4,), jnp.int32)
    puts = []
    with recording(path):
        while rt.total_size() > 0:
            before = spans.snapshot()["counters"].get("steal.enqueue_puts", 0)
            carry = rt.run_fused(4, body, carry,
                                 until_drained=mode == "drained")[0]
            puts.append(spans.snapshot()["counters"]["steal.enqueue_puts"]
                        - before)
    return puts


@pytest.fixture(scope="module")
def mesh_puts():
    """``_puts_per_dispatch`` on ``MeshStealRuntime`` over four CPU
    devices, in a subprocess that sets them up, for each mode."""
    script = ('import json, sys, tempfile\n'
              f'sys.path.insert(0, {os.path.dirname(__file__)!r})\n'
              'from test_spans import _puts_per_dispatch\n'
              'out = {m: _puts_per_dispatch("mesh", m, tempfile.mkdtemp())\n'
              '       for m in ("drained", "scan")}\n'
              'print("PUTS " + json.dumps(out))\n')
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("PUTS ")]
    assert line, out.stderr[-2000:]
    return json.loads(line[0][len("PUTS "):])


@pytest.mark.parametrize("execution", ["vmap", "mesh"])
@pytest.mark.parametrize("mode", ["drained", "scan"])
def test_enqueue_puts_two_then_none(execution, mode, tmp_path, request):
    """A fresh runtime's first fused dispatch puts the proportion and the
    round index; every later dispatch of a steady drain starts from the
    values the one before left on the devices."""
    if execution == "vmap" or jax.device_count() >= 4:
        puts = _puts_per_dispatch(execution, mode, tmp_path)
    else:
        puts = request.getfixturevalue("mesh_puts")[mode]
    assert len(puts) > 2
    assert puts == [2] + [0] * (len(puts) - 1)


@pytest.mark.parametrize("mode", ["drained", "scan", "round"])
def test_theft_counters_equal_the_telemetry(mode, tmp_path):
    """``steal.thefts`` and ``steal.items_moved`` are the per-round
    ``n_steals`` and ``n_transferred`` the runtime records, summed."""
    rt = _make_rt()
    with recording(tmp_path):
        _drive(rt, mode)
    counters = spans.snapshot()["counters"]
    thefts = sum(rec.n_steals for rec in rt.telemetry.rounds)
    moved = sum(rec.n_transferred for rec in rt.telemetry.rounds)
    assert thefts > 0 and moved > 0
    assert counters["steal.thefts"] == thefts
    assert counters["steal.items_moved"] == moved


def test_compile_fires_once_per_new_signature(tmp_path):
    fn = LiftedJit(lambda x: x * 2 + 1)
    with recording(tmp_path):
        for _ in range(3):
            fn(jnp.arange(4, dtype=jnp.int32))
            fn(jnp.arange(8, dtype=jnp.int32))
    snap = spans.snapshot()
    assert snap["spans"]["steal:compile"]["n"] == 2
    assert snap["counters"]["steal.compiles"] == 2
    assert fn._cache_size() == 2


def test_two_solves_count_two_calls_one_compile_one_reuse(tmp_path):
    inst = random_instance(8, seed=3)
    parallel._PROGRAMS.clear()
    with recording(tmp_path):
        for _ in range(2):
            opt, stats = parallel_solve(inst, n_workers=4)
            assert opt == dp_solve(inst)
    snap = spans.snapshot()
    assert snap["counters"]["solve.calls"] == 2
    # The second call runs the first one's program.
    assert snap["counters"]["steal.compiles"] == 1
    assert snap["counters"]["solve.programs_reused"] == 1
    for name in ("solve:call", "solve:build", "solve:result"):
        assert snap["spans"][name]["n"] == 2
    assert snap["spans"]["steal:compile"]["n"] == 1
    # steal:compile runs inside the first dispatch's enqueue.
    assert (snap["spans"]["steal:enqueue"]["self_s"]
            < snap["spans"]["steal:enqueue"]["total_s"])
    assert snap["counters"]["steal.rounds"] == 2 * stats["supersteps"]


def test_full_buffer_counts_what_it_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "CAPACITY", 5)
    with recording(tmp_path):
        for _ in range(4):
            with spans.span("outer"):
                with spans.span("inner"):
                    pass
    assert len(spans.recorded()) == 5
    snap = spans.snapshot()
    assert snap["counters"]["spans.dropped"] == 3
    # The totals keep counting whatever the buffer holds.
    assert snap["spans"]["outer"]["n"] == snap["spans"]["inner"]["n"] == 4
    assert spans.recorded()[0][3] == "outer"  # inner's parent
    spans.reset()
    assert spans.recorded() == [] and spans.snapshot()["counters"] == {}


def test_threads_lose_no_span_or_count(tmp_path):
    threads, each = 16, 300
    interval = sys.getswitchinterval()

    def work():
        for _ in range(each):
            with spans.span("outer"):
                with spans.span("inner"):
                    spans.count("work")

    sys.setswitchinterval(1e-6)
    try:
        with recording(tmp_path):
            pool = [threading.Thread(target=work) for _ in range(threads)]
            for t in pool:
                t.start()
            for t in pool:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    snap = spans.snapshot()
    assert snap["counters"]["work"] == threads * each
    assert snap["spans"]["outer"]["n"] == snap["spans"]["inner"]["n"] \
        == threads * each
    # Each thread nests its own spans: every inner closed under an outer.
    assert all(r[3] == "outer" for r in spans.recorded()
               if r[0] == "inner")


def test_spans_share_the_profilers_clock(tmp_path):
    """The buffered spans start, at the median, within 50 us of the
    TraceAnnotation events they opened, on the trace's absolute clock
    (``profile_start_time`` plus the event's offset)."""
    from jax.profiler import ProfileData

    with recording(tmp_path):
        for _ in range(50):
            with spans.span("steal:outer"):
                with spans.span("steal:inner"):
                    jnp.arange(4).block_until_ready()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    start, events = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            events += [(ev.name, ev.start_ns) for ev in line.events
                       if ev.name.startswith("steal:")]
    events.sort(key=lambda e: e[1])
    buffered = sorted(spans.recorded(), key=lambda r: r[1])
    assert [e[0] for e in events] == [r[0] for r in buffered]
    gaps = [abs(r[1] - (start + e[1])) for e, r in zip(events, buffered)]
    assert np.median(gaps) < 50_000


def test_metrics_expose_span_totals_and_counters(tmp_path):
    rt = _make_rt()
    assert "repro_span_seconds_total" not in rt.metrics().snapshot()
    with recording(tmp_path):
        _drive(rt, "drained")
    text = rt.metrics().to_prometheus()
    assert "# TYPE repro_span_seconds_total counter" in text
    assert 'repro_span_seconds_total{span="steal:run_fused"}' in text
    assert 'repro_span_self_seconds_total{span="steal:wait"}' in text
    n = spans.snapshot()["spans"]["steal:run_fused"]["n"]
    assert f'repro_span_calls_total{{span="steal:run_fused"}} {n}' in text
    assert (f'repro_span_events_total{{counter="steal.rounds"}} '
            f'{rt.rounds_run}') in text


# -- named scopes in the compiled round ----------------------------------------

_SCOPE_CHECK = textwrap.dedent("""
    import re
    import jax
    import jax.numpy as jnp
    from repro.core.policy import StealPolicy
    from repro.distributed import launch_runtime

    def op_names(execution, body_of, **lane):
        rt = launch_runtime(4, 256, jax.ShapeDtypeStruct((), jnp.int32),
                            execution=execution, max_pop=8,
                            policy=StealPolicy(queue_limit=2, low_watermark=1,
                                               high_watermark=8,
                                               max_steal=64), **lane)
        lowered = rt.lower(body_of(rt.ops), jnp.zeros((4,), jnp.int32),
                           fused=8)
        text = lowered.compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    def scopes_found(names, scopes):
        return {s for s in scopes if any(
            re.search(r"(^|[/(])%s($|[/)])" % re.escape(s), n)
            for n in names)}
""")


def _found(execution, **lane):
    ns = {}
    exec(compile(_SCOPE_CHECK, "<scope-check>", "exec"), ns)
    return ns["scopes_found"](ns["op_names"](execution, _body, **lane),
                              SCOPES)


# Under vmap a flat size all_gather is the stacked sizes themselves: it
# lowers to no instruction, so master.sizes has nothing to name there.
# The pod grid's gathers over both axes do lower, and keep the name.
@pytest.mark.parametrize("lane, absent", [
    ({}, {"master.sizes"}),
    ({"fault_plan": FaultPlan()}, {"master.sizes"}),
    ({"pod_size": 2, "fault_plan": FaultPlan()}, set()),
], ids=["plain", "resilient", "resilient-pods"])
def test_named_scopes_reach_the_compiled_round_vmap(lane, absent):
    assert _found("vmap", **lane) == set(SCOPES) - absent


def test_named_scopes_reach_the_compiled_round_mesh():
    script = ('import os\n'
              'os.environ["XLA_FLAGS"] = '
              '"--xla_force_host_platform_device_count=4"\n'
              'import sys\n'
              f'sys.path.insert(0, {os.path.dirname(__file__)!r})\n'
              'from test_spans import _body, SCOPES\n'
              + _SCOPE_CHECK
              + 'found = scopes_found(op_names("mesh", _body), SCOPES)\n'
              'print("SCOPES", sorted(found))\n')
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("SCOPES")]
    assert line, out.stderr[-2000:]
    assert line[0] == f"SCOPES {sorted(SCOPES)}"
