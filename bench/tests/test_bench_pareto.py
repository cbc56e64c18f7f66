"""The ``fig9-pareto.irregular`` cell: it resolves from new files alone,
its per-layer metrics apply to it alone, its readers give the number
their docstrings promise (and ``None`` on a program that records
nothing), the plain reference's cost table is the program's, and the
comparison with the reference catches every fault planted under the
timed path at a size a CPU holds."""

import contextlib
import sys

import numpy as np
import pytest

from bench import faults, harness, jobs, pareto

SPEC = harness.load_spec()
CELL = "fig9-pareto.irregular"
METRICS = ("slot_busy.pareto", "inflight_rounds.pareto",
           "starved_lanes.pareto", "stolen_share.pareto",
           "device_idle.pareto", "host_wait_us.pareto")
SEED = 2**31 + 67890
# The cell's law and driver at a size a CPU holds: 2,000 nodes costing 1
# to 63 units, 4 lanes of 16 slots, rings of 256.
SMALL = {"config": {"nodes": 2000, "slots": 16, "ring_slots": 256,
                    "backend": "reference",
                    "cost": {"law": "bounded_pareto", "alpha": 1.1, "k": 1,
                             "p": 64, "rounding": "floor"}},
         "layout": {"lanes": 4}}


def test_cell_resolves_with_its_metrics():
    found = harness.resolve(SPEC, CELL)
    assert found["cell"]["chips"] == 1
    assert (found["layout"]["lanes"], found["layout"]["execution"]) == (
        8, "vmap")
    assert found["traffic"]["kind"] == "pareto_drain"
    assert [m["name"] for m in found["end_to_end"]] == ["nodes_per_s",
                                                        "setup_s"]
    assert sorted(m["name"] for m in found["per_layer"]) == sorted(METRICS)
    config = found["config"]
    assert (config["nodes"], config["fanout"], config["slots"]) == (
        2_500_000, 4, 512)
    assert config["cost"] == {"law": "bounded_pareto", "alpha": 1.1, "k": 1,
                              "p": 1024, "rounding": "floor"}
    job = jobs.make(config, found["traffic"], found["layout"], seed=SEED)
    assert job.root_lane == SEED % 8
    # 4 x (12.72M units over 4,096 slots, a last node's 1,023, depth 11).
    assert job.max_rounds == 4 * (3105 + 1023 + 11)
    assert job.kernel_rows() == {512: "pop", 16_384: "ring_write",
                                 8192: "window"}


@pytest.mark.parametrize("metric", METRICS)
def test_metric_applies_to_the_cell_alone(metric):
    for cell in SPEC["workloads"]:
        names = {m["name"]
                 for m in harness.resolve(SPEC, cell["name"])["per_layer"]}
        assert (metric in names) is (cell["name"] == CELL)


@pytest.mark.parametrize("law", [(1.1, 1, 1024), (1.1, 1, 64), (1.5, 2, 100)])
def test_reference_table_is_the_programs(law):
    from repro.core.irregular import ParetoCost

    table = ParetoCost(*law).table()
    assert pareto.thresholds(*law) == table.tolist()
    _, k, p = law
    edges = np.concatenate([[0], table, [2**31]]).astype(np.float64)
    mean = (np.diff(edges) / 2**31 * np.arange(k, p)).sum()
    assert pareto.mean_cost(*law) == pytest.approx(mean, rel=1e-12)


@pytest.mark.parametrize("n,fanout,depth", [(1, 4, 0), (2, 4, 1), (5, 4, 1),
                                            (6, 4, 2), (2_500_000, 4, 11)])
def test_tree_depth(n, fanout, depth):
    driver = jobs.load_driver("pareto_drain")
    assert driver.tree_depth(n, fanout) == depth


COUNTERS = {"rounds": 400, "lane_rounds": 3200, "starved_lane_rounds": 80,
            "busy_slot_rounds": 1_228_800, "slot_rounds": 1_638_400,
            "inflight_only_rounds": 60, "popped": 200_000}


@pytest.mark.parametrize("name,expected", [
    ("slot_busy.pareto", 75.0),
    ("inflight_rounds.pareto", 15.0),
    ("starved_lanes.pareto", 2.5),
])
def test_counter_reader_on_known_counters(name, expected):
    assert harness.load_reader(name)({"counters": COUNTERS}) == (
        pytest.approx(expected))
    assert harness.load_reader(name)({"counters": {}}) is None


SNAPSHOT = {"spans": {"steal:wait": {"n": 50, "total_s": 0.2,
                                     "self_s": 0.2}},
            "counters": {"steal.rounds": 400, "steal.items_moved": 9000}}


def _with_snapshot(monkeypatch, snap):
    from repro.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: snap)


@pytest.mark.parametrize("name,expected", [
    ("stolen_share.pareto", 100.0 * 9000 / 200_000),
    ("host_wait_us.pareto", 1e6 * 0.2 / 400),
])
def test_span_reader_on_a_known_snapshot(name, expected, monkeypatch):
    _with_snapshot(monkeypatch, SNAPSHOT)
    value = harness.load_reader(name)({"counters": COUNTERS})
    assert value == pytest.approx(expected)


@pytest.mark.parametrize("name", ["stolen_share.pareto",
                                  "host_wait_us.pareto"])
def test_span_reader_finds_nothing(name, monkeypatch):
    """An empty record, or a program older than the span recorder, gives
    no number and no error."""
    import repro.obs

    ctx = {"counters": COUNTERS}
    _with_snapshot(monkeypatch, {"spans": {}, "counters": {}})
    assert harness.load_reader(name)(ctx) is None
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr(repro.obs, "spans", raising=False)
    assert harness.load_reader(name)(ctx) is None


def test_idle_reader_reads_device_zero():
    ctx = {"trace": {"window_s": 2.0, "devices": {"1": {"busy_s": 2.0},
                                                  "0": {"busy_s": 0.5}}}}
    assert harness.load_reader("device_idle.pareto")(ctx) == 75.0
    assert harness.load_reader("device_idle.pareto")({"trace": None}) is None


def run(plant=None, seconds=0.5):
    ctx = faults.PLANTS[plant]() if plant else contextlib.nullcontext()
    with ctx:
        return harness.run_cell(CELL, SEED, seconds, False,
                                require_tpu=False, overrides=SMALL)


def test_driver_runs_at_cpu_size_and_every_check_reads_zero():
    result = run()
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert {c["value"] for c in result["checks"].values()} == {0}
    assert set(result["checks"]) == {
        "nodes_lost_or_repeated", "units_wrong",
        "drains_with_wrong_checksum", "items_left_in_queues_or_in_flight"}
    assert set(result["metrics"]) == {"nodes_per_s", "setup_s"}
    counts = result["window_counts"]
    assert counts["item_bytes"] == 4
    assert counts["slot_rounds"] == counts["rounds"] * 4 * 16
    assert 0 < counts["busy_slot_rounds"] <= counts["slot_rounds"]
    assert 0 < counts["inflight_only_rounds"] < counts["rounds"]
    ref = pareto.reference(2000, 4, 1.1, 1, 64, pareto.salt_for(SEED))
    assert counts["units"] <= ref["units"] * (counts["drains_closed"] + 1)
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("plant", ["state_unchanged", "half_batch",
                                   "exchange_dropped", "answer_altered"])
def test_cell_catches_faults(plant):
    result = run(plant)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_salt_differs_by_seed_and_the_checksum_with_it():
    salts = {pareto.salt_for(s) for s in (1, 2, SEED)}
    assert len(salts) == 3
    sums = {pareto.reference(300, 4, 1.1, 1, 64, s)["hash_sum"]
            for s in salts}
    assert len(sums) == 3
