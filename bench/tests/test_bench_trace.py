"""The reduction from a device trace to the per-layer numbers, on
synthetic events and on a trace recorded on a TPU v5e."""

import json
import os

import pytest

from bench import kernel_bytes, readers, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "drain_trace.xplane.pb.gz")
EXPECTED = os.path.join(DATA, "drain_trace.expected.json")
# fig9-dag's geometry: pop batch 64, ring 16,384 slots, max_steal 1,024.
DAG_ROWS = {64: "pop", 16384: "ring_write", 1024: "window"}


def test_describe_real_instructions():
    pop = ('%closed_call.42 = s32[64,1]{1,0:T(8,128)S(1)} custom-call('
           's32[1]{0:T(128)} %bitcast.111), custom_call_target='
           '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert trace_reduce.describe(pop, DAG_ROWS) == (
        "closed_call custom-call s32[64,1]{1,0:T(8,128)S(1)}",
        ["queue_kernel.pop"])
    alloc = ('%custom-call.24 = s32[8,64,1]{1,2,0:T(1,128)S(1)} '
             'custom-call(), custom_call_target="AllocateBuffer"')
    assert trace_reduce.describe(alloc, DAG_ROWS)[1] == []
    gather = ('%all-gather.3 = s32[4]{0} all-gather(s32[1]{0} %p), '
              'replica_groups={{0,1,2,3}}, dimensions={0}')
    assert trace_reduce.describe(gather, DAG_ROWS) == (
        "all-gather all-gather s32[4]{0}", ["collective"])
    loop = '%while.98 = (s32[]{:T(128)}, s32[8]{0}) while((s32[]) %t)'
    assert trace_reduce.describe(loop, DAG_ROWS)[0] == "while while (s32[]{:T(128)},"


def test_union_gaps_self_times_and_labels():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    assert trace_reduce.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert trace_reduce.gaps([], 0, 10) == [(0, 10)]
    own = trace_reduce.self_times([("loop", 0, 10), ("a", 1, 2),
                                   ("b", 4, 3), ("c", 5, 1), ("d", 20, 1)])
    assert [d for _, _, d in own] == [5, 2, 2, 1, 1]
    host = [("bench:window", 0, 100), ("outer", 0, 60), ("inner", 10, 5)]
    assert trace_reduce.labels(host, [12, 40, 80]) == [
        "inner", "outer", "bench:window"]


def synthetic():
    """A 100 us window.  Chip 0: a loop from 0 to 30 us holding a pop
    kernel (10-20 us), an all-gather at 50-60 us, a ring write at 95-105
    us (cut at 100) and an op outside the window; chip 1 busy 0-40 us.
    The host is in ``bench:run_fused`` from 0 to 50 us and in
    ``readback`` from 70 to 90 us."""
    us = 1000.0
    return {
        "window_ns": [0.0, 100 * us],
        "devices": {
            "0": [("while while (s32[])", 0.0, 30 * us, []),
                  ("closed_call custom-call s32[64,1]", 10 * us, 10 * us,
                   ["queue_kernel.pop"]),
                  ("all-gather all-gather s32[4]", 50 * us, 10 * us,
                   ["collective"]),
                  ("closed_call custom-call s32[16384,1]", 95 * us, 10 * us,
                   ["queue_kernel.ring_write"]),
                  ("fusion fusion s32[8]", 200 * us, 5 * us, [])],
            "1": [("fusion fusion s32[8]", 0.0, 40 * us, [])],
        },
        "host": [("bench:window", 0.0, 100 * us),
                 ("bench:run_fused", 0.0, 50 * us),
                 ("readback", 70 * us, 20 * us)],
    }


def test_reduce_synthetic_trace():
    red = trace_reduce.reduce(synthetic(), chips=2)
    assert red["window_s"] == pytest.approx(100e-6)
    dev0, dev1 = red["devices"]["0"], red["devices"]["1"]
    assert dev0["busy_s"] == pytest.approx(45e-6)   # 0-30, 50-60, 95-100
    assert dev1["busy_s"] == pytest.approx(40e-6)
    assert red["busy_s"] == pytest.approx(42.5e-6)
    assert dev0["op_s"]["while while (s32[])"] == pytest.approx(20e-6)
    assert dev0["class_s"]["queue_kernel.pop"] == pytest.approx(10e-6)
    assert dev0["class_n"]["collective"] == 1
    # Idle on chip 0: 30-50 (host in run_fused) and 60-95 (readback at
    # the midpoint 77.5).
    assert red["gaps"] == [["readback", pytest.approx(35e-6)],
                           ["bench:run_fused", pytest.approx(20e-6)]]
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0] == ["while while (s32[])",
                                   pytest.approx(20e-6)]
    assert [n for n, _ in bd["idle_gaps"]] == ["readback", "bench:run_fused"]


def test_readers_on_synthetic_trace():
    red = trace_reduce.reduce(synthetic(), chips=1)
    ctx = {"trace": red, "chips": 1, "peaks": {"hbm_bytes_per_s": 1e9},
           "counters": {"rounds": 5, "lane_rounds": 40,
                        "starved_lane_rounds": 10, "popped": 100,
                        "pushed": 50, "transferred": 0, "item_bytes": 4}}
    assert readers.idle_percent(ctx) == pytest.approx(55.0)
    assert readers.starved_percent(ctx) == pytest.approx(25.0)
    kinds = readers.queue_kernel_kinds(ctx)
    assert kinds == {"pop": pytest.approx(10e-6),
                     "ring_write": pytest.approx(5e-6)}
    assert readers.per_round_us(sum(kinds.values()), ctx) == pytest.approx(3)
    assert kernel_bytes.queue_kernel_bytes(ctx["counters"], kinds) == 1200
    assert readers.idle_percent({"trace": None}) is None


def summary(red: dict) -> dict:
    dev = red["devices"]["0"]
    return {"window_s": red["window_s"], "busy_s": red["busy_s"],
            "class_s": dev["class_s"], "class_n": dev["class_n"],
            "n_gaps": len(red["gaps"]),
            "top_ops": sorted(dev["op_s"].items(), key=lambda x: -x[1])[:5],
            "idle_by_label": trace_reduce.breakdown(red)["idle_gaps"]}


def test_recorded_trace_reduces_the_same_way():
    """A 0.25 s window of ``fig9-dag.drain`` recorded on a TPU v5e: the
    reduction must give the numbers it gave when the benchmark was
    defined."""
    red = trace_reduce.reduce(trace_reduce.extract(RECORDED, DAG_ROWS), 1)
    got = json.loads(json.dumps(summary(red)))
    with open(EXPECTED) as f:
        want = json.load(f)
    assert got == want
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(sec for _, sec in red["gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
