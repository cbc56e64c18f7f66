"""The readers of the ``program_span`` metrics: the number each gives on a
known snapshot of the program's spans, and ``None`` where the program
recorded nothing or has no span recorder at all."""

import sys

import pytest

from bench import harness

SNAPSHOT = {
    "spans": {
        "steal:run_fused": {"n": 10, "total_s": 0.100, "self_s": 0.001},
        "steal:enqueue": {"n": 10, "total_s": 0.030, "self_s": 0.008},
        "steal:compile": {"n": 2, "total_s": 0.022, "self_s": 0.022},
        "steal:wait": {"n": 10, "total_s": 0.020, "self_s": 0.020},
        "steal:readback": {"n": 10, "total_s": 0.040, "self_s": 0.040},
        "steal:record": {"n": 10, "total_s": 0.009, "self_s": 0.009},
        "solve:call": {"n": 2, "total_s": 0.5, "self_s": 0.1},
        "solve:build": {"n": 2, "total_s": 0.300, "self_s": 0.300},
        "solve:result": {"n": 2, "total_s": 0.004, "self_s": 0.004},
    },
    "counters": {"steal.rounds": 80, "steal.dispatches": 10,
                 "steal.compiles": 2, "solve.calls": 2},
}

EXPECTED = {
    "host_enqueue_us.drain": 1e6 * 0.008 / 80,
    "host_wait_us.drain": 1e6 * 0.020 / 80,
    "host_readback_us.drain": 1e6 * 0.040 / 80,
    "host_record_us.drain": 1e6 * 0.009 / 80,
    "call_overhead_ms.solve": 1e3 * (0.300 + 0.022 + 0.004) / 2,
    "host_round_us.solve": 1e6 * (0.008 + 0.040 + 0.009) / 80,
}

EMPTY = {"spans": {}, "counters": {}}


def _with_snapshot(monkeypatch, snap):
    from repro.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: snap)


def test_span_metrics_are_declared():
    declared = {m["name"]: m for m in harness.load_spec()["per_layer"]}
    for name in EXPECTED:
        assert declared[name]["source"] == "program_span"


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_known_snapshot(name, monkeypatch):
    _with_snapshot(monkeypatch, SNAPSHOT)
    assert harness.load_reader(name)({}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_an_empty_snapshot(name, monkeypatch):
    _with_snapshot(monkeypatch, EMPTY)
    assert harness.load_reader(name)({}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_a_span_recorder(name, monkeypatch):
    """A program older than the span recorder gives no number and no
    error."""
    import repro.obs

    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr(repro.obs, "spans", raising=False)
    assert harness.load_reader(name)({}) is None
