"""The ``uts-t3.search`` cell: it resolves from new files alone, its
per-layer metrics apply to it alone, its readers give the number their
docstrings promise (and ``None`` on a program that records nothing), the
plain reference reproduces ``hashlib`` walks of UTS trees, and the
comparison with the reference catches every fault planted under the
timed path at a size a CPU holds."""

import contextlib
import sys

import pytest

from bench import faults, harness, jobs, uts
from bench.trace_reduce import QUEUE_KERNEL

SPEC = harness.load_spec()
CELL = "uts-t3.search"
# The drain's readers that the search cell's host-loop and kernel metrics
# re-export, by the search cell's metric.
REEXPORTED = {f"{name}.uts": f"{name}.drain" for name in (
    "host_enqueue_us", "host_wait_us", "host_readback_us", "host_record_us",
    "queue_kernel_us", "queue_kernel_roofline")}
METRICS = ("stolen_share.uts", "thefts_per_round.uts", "starved_lanes.uts",
           "device_idle.uts", *REEXPORTED)
SEED = 2**31 + 54321
# T3's rules on a small root: b0 = 100, r = 4 (a hashlib walk: 14,941
# nodes, 13,085 leaves, depth 71).
SMALL_T3 = {"config": {"b0": 100, "r": 4, "nodes": 14941, "leaves": 13085,
                       "depth": 71}}


def test_cell_resolves_with_its_metrics():
    found = harness.resolve(SPEC, CELL)
    assert found["cell"]["chips"] == 1
    assert (found["layout"]["lanes"], found["layout"]["execution"]) == (
        8, "vmap")
    assert found["traffic"]["kind"] == "uts_search"
    assert [m["name"] for m in found["end_to_end"]] == ["nodes_per_s",
                                                        "setup_s"]
    assert sorted(m["name"] for m in found["per_layer"]) == sorted(METRICS)
    config = found["config"]
    assert (config["b0"], config["q"], config["m"], config["r"]) == (
        2000, 0.124875, 8, 42)
    assert (config["nodes"], config["leaves"], config["depth"]) == (
        4112897, 3599034, 1572)
    job = jobs.make(config, found["traffic"], found["layout"], seed=SEED)
    assert job.root_lane == SEED % 8


@pytest.mark.parametrize("metric", METRICS)
def test_metric_applies_to_the_cell_alone(metric):
    for cell in SPEC["workloads"]:
        names = {m["name"]
                 for m in harness.resolve(SPEC, cell["name"])["per_layer"]}
        assert (metric in names) is (cell["name"] == CELL)


SNAPSHOT = {"spans": {"steal:run_fused": {"n": 5, "total_s": 0.05,
                                          "self_s": 0.001}},
            "counters": {"steal.rounds": 40, "steal.thefts": 30,
                         "steal.items_moved": 900}}


def _with_snapshot(monkeypatch, snap):
    from repro.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: snap)


@pytest.mark.parametrize("name,expected", [
    ("stolen_share.uts", 100.0 * 900 / 3000),
    ("thefts_per_round.uts", 30 / 40),
])
def test_span_reader_on_a_known_snapshot(name, expected, monkeypatch):
    _with_snapshot(monkeypatch, SNAPSHOT)
    ctx = {"counters": {"popped": 3000}}
    assert harness.load_reader(name)(ctx) == pytest.approx(expected)


# A traced window of 8 rounds: host spans, and 3 ms of queue kernels on
# device 0 (1 ms of each kind) in a 10 ms window.
TRACED_SNAPSHOT = {
    "spans": {"steal:enqueue": {"n": 1, "total_s": 0.004, "self_s": 0.003},
              "steal:wait": {"n": 1, "total_s": 0.002, "self_s": 0.002},
              "steal:readback": {"n": 1, "total_s": 0.005, "self_s": 0.005},
              "steal:record": {"n": 1, "total_s": 0.0016,
                               "self_s": 0.0016}},
    "counters": {"steal.rounds": 8}}
TRACED_CTX = {
    "trace": {"window_s": 0.01, "devices": {"0": {
        "busy_s": 0.004,
        "class_s": {QUEUE_KERNEL + kind: 0.001
                    for kind in ("pop", "ring_write", "window")}}}},
    "counters": {"rounds": 8, "popped": 512, "pushed": 520,
                 "transferred": 40, "item_bytes": 24},
    "peaks": {"hbm_bytes_per_s": 8.19e11}, "chips": 1}


@pytest.mark.parametrize("name,expected", [
    ("host_enqueue_us.uts", 375.0),
    ("host_wait_us.uts", 250.0),
    ("host_readback_us.uts", 625.0),
    ("host_record_us.uts", 200.0),
    ("queue_kernel_us.uts", 375.0),
    ("queue_kernel_roofline.uts",
     100.0 * 2 * 24 * (512 + 560 + 40) / (0.003 * 8.19e11)),
])
def test_reexported_reader_reads_as_the_drains(name, expected, monkeypatch):
    """The search cell's host-loop and kernel metrics read its spans and
    its 24-byte rows exactly as the drain's readers read the drain's."""
    _with_snapshot(monkeypatch, TRACED_SNAPSHOT)
    value = harness.load_reader(name)(TRACED_CTX)
    assert value == pytest.approx(expected)
    assert value == harness.load_reader(REEXPORTED[name])(TRACED_CTX)


@pytest.mark.parametrize("name", ["stolen_share.uts",
                                  "thefts_per_round.uts",
                                  *(n for n in REEXPORTED
                                    if n.startswith("host_"))])
def test_span_reader_finds_nothing(name, monkeypatch):
    """An empty record, or a program older than the span recorder, gives
    no number and no error."""
    import repro.obs

    ctx = {"counters": {"popped": 3000}}
    _with_snapshot(monkeypatch, {"spans": {}, "counters": {}})
    assert harness.load_reader(name)(ctx) is None
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr(repro.obs, "spans", raising=False)
    assert harness.load_reader(name)(ctx) is None


@pytest.mark.parametrize("tree,shape", [
    ((100, 0.124875, 8, 7), (5989, 5252, 63)),
    ((100, 0.124875, 8, 4), (14941, 13085, 71)),
])
def test_reference_reproduces_hashlib_walks(tree, shape):
    ref = uts.reference(*tree, salt=uts.salt_for(SEED))
    assert (ref["nodes"], ref["leaves"], ref["depth"]) == shape
    assert ref["hash_sum"] != uts.reference(*tree, salt=1)["hash_sum"]


def run(overrides, plant=None, seconds=0.5):
    ctx = faults.PLANTS[plant]() if plant else contextlib.nullcontext()
    with ctx:
        return harness.run_cell(CELL, SEED, seconds, False,
                                require_tpu=False, overrides=overrides)


@pytest.mark.parametrize("plant", [None, "state_unchanged", "half_batch",
                                   "exchange_dropped", "answer_altered"])
def test_search_cell_catches_faults(plant):
    result = run(SMALL_T3, plant)
    if plant is None:
        assert result["correct"] is True, result["checks"]
        assert result["failed"] == 0
        assert set(result["metrics"]) == {"nodes_per_s", "setup_s"}
    else:
        assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1
    assert result["window_counts"]["item_bytes"] == 24
    assert list(result)[-1] == "checks"
