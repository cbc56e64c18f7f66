"""``parallel_solve`` reuses its compiled programs across calls of one
geometry: a second instance runs the first one's fused block with no new
trace and gives what a fresh program gives; a new geometry, backend,
execution mode or patched queue op traces anew; the sanitizer bypasses
the table."""

import contextlib

import jax
import pytest

from repro.analysis import sanitize
from repro.core.dd import parallel
from repro.core.dd.knapsack import dp_solve, random_instance
from repro.core.dd.parallel import parallel_solve
from repro.core.ops import BulkOps
from repro.obs import spans

# Small enough for the CPU, with steals: seed 1 takes 7 supersteps and
# moves 6 subproblems between lanes.
SOLVER = dict(n_workers=4, explore_width=2, batch=2)
RESULT = ("supersteps", "explored", "per_worker_explored", "transferred")


@pytest.fixture(autouse=True, scope="module")
def _leave_no_programs():
    yield
    parallel._PROGRAMS.clear()


@pytest.fixture
def empty_table():
    parallel._PROGRAMS.clear()


@contextlib.contextmanager
def recording(path):
    """A profiler session, the switch that turns span recording on."""
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _counts(path, inst, **kwargs):
    """Solve ``inst`` under recording; returns (optimum, stats, the
    call's ``steal.compiles`` and ``solve.programs_reused``)."""
    spans.reset()
    with recording(path):
        opt, stats = parallel_solve(inst, **kwargs)
    counters = spans.snapshot()["counters"]
    assert counters["solve.calls"] == 1
    return (opt, stats, counters.get("steal.compiles", 0),
            counters.get("solve.programs_reused", 0))


def test_second_instance_reuses_the_program(tmp_path, empty_table):
    first, second = random_instance(8, seed=4), random_instance(8, seed=1)
    assert first != second
    opt, _, compiles, reused = _counts(tmp_path, first, **SOLVER)
    assert (opt, compiles, reused) == (dp_solve(first), 1, 0)

    opt, stats, compiles, reused = _counts(tmp_path, second, **SOLVER)
    assert (opt, compiles, reused) == (dp_solve(second), 0, 1)

    parallel._PROGRAMS.clear()
    fresh_opt, fresh, compiles, reused = _counts(tmp_path, second, **SOLVER)
    assert (fresh_opt, compiles, reused) == (opt, 1, 0)
    assert stats["transferred"] > 0
    for name in RESULT:
        assert stats[name] == fresh[name], name


BASE = dict(n=8, n_workers=1, backend="reference", execution="vmap")


@pytest.mark.parametrize("change", [{"n": 9}, {"n_workers": 2},
                                    {"backend": "pallas"},
                                    {"execution": "mesh"}])
def test_new_geometry_traces_anew(change, tmp_path):
    def solve(n, **kwargs):
        inst = random_instance(n, seed=1)
        opt, stats, compiles, reused = _counts(
            tmp_path, inst, explore_width=2, batch=2, **kwargs)
        assert opt == dp_solve(inst)
        return stats, compiles, reused

    solve(**BASE)  # traced by the first case, reused by the others
    stats, compiles, reused = solve(**{**BASE, **change})
    # (A mesh solve traces its block twice: the carry comes back
    # sharded over the mesh, a new signature for the second dispatch.)
    assert compiles >= 1 and reused == 0
    if "execution" in change:
        assert stats["execution"] == "mesh"
    # The earlier geometry's programs are still kept.
    assert solve(**BASE)[1:] == (0, 1)


def test_patched_queue_op_is_traced_anew(tmp_path, monkeypatch,
                                         empty_table):
    inst = random_instance(8, seed=1)
    assert _counts(tmp_path, inst, **SOLVER)[2:] == (1, 0)
    assert _counts(tmp_path, inst, **SOLVER)[2:] == (0, 1)

    orig, traced = BulkOps.pop_bulk, []

    def pop_bulk(self, q, max_n, n, **kwargs):
        traced.append(max_n)
        return orig(self, q, max_n, n, **kwargs)

    monkeypatch.setattr(BulkOps, "pop_bulk", pop_bulk)
    opt, _, compiles, reused = _counts(tmp_path, inst, **SOLVER)
    assert (opt, compiles, reused) == (dp_solve(inst), 1, 0)
    assert traced  # the new program was traced through the patch


def test_sanitizer_bypasses_the_table(tmp_path, monkeypatch, empty_table):
    monkeypatch.setenv("REPRO_CHECK", "1")
    sanitize.reset_violations()
    for seed in (4, 1):
        inst = random_instance(8, seed=seed)
        opt, _, compiles, reused = _counts(tmp_path, inst, **SOLVER)
        assert (opt, compiles, reused) == (dp_solve(inst), 1, 0)
    assert not parallel._PROGRAMS
    assert sanitize.violations() == ()
