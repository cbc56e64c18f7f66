"""Observability package coverage: zero recompiles on a repeated drive,
trace export + validation, metrics registry / exposition / collectors,
the resilient-run textfile, wall-clock failure detection, PagedQueue
spill accounting, and the perf-trend gate.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import trend
from repro.core.policy import StealPolicy
from repro.core.queue import PagedQueue
from repro.distributed.elastic import compile_count
from repro.obs.metrics import MetricsRegistry, write_textfile
from repro.obs.trace import export_trace, validate_trace
from repro.runtime import FaultPlan, StealRuntime
from repro.runtime.detector import DetectorPolicy, FailureDetector

SPEC = {"x": jax.ShapeDtypeStruct((), jnp.int32)}


def _make_rt(**kw):
    kw.setdefault("policy", StealPolicy(low_watermark=1, high_watermark=8))
    return StealRuntime(4, 64, SPEC, max_pop=4, **kw)


def _seed(rt, n=48):
    rt.push(0, {"x": jnp.arange(n, dtype=jnp.int32)}, n)


def _body(ops):
    def body(q, carry):
        q, _batch, n = ops.pop_bulk(q, 4, jnp.int32(2))
        return q, carry + n

    return body


def _drive(rt, *, rounds=5, fused=2, body=None):
    carry = jnp.zeros((rt.n_workers,), jnp.int32)
    body = body or _body(rt.ops)
    for _ in range(rounds):
        carry, _ = rt.round(body, carry)
    carry, _ = rt.run_fused(fused, body, carry)
    return carry


# -- compiled programs -------------------------------------------------------


def test_second_drive_compiles_nothing():
    """The same worker body driven again through ``round`` and
    ``run_fused`` reuses the programs the first drive compiled, with the
    fault layer and the detector armed."""
    rt = _make_rt(fault_plan=FaultPlan())
    rt.attach_detector(DetectorPolicy(suspect_after=2))
    body = _body(rt.ops)
    _seed(rt)
    _drive(rt, body=body)
    programs, compiles = set(rt._compiled), compile_count(rt)
    _seed(rt)
    _drive(rt, body=body)
    assert set(rt._compiled) == programs
    assert compile_count(rt) == compiles


# -- trace export ------------------------------------------------------------


def _traced_telemetry():
    rt = _make_rt(fault_plan=FaultPlan(kills=((3, 4),)))
    rt.attach_detector(DetectorPolicy(suspect_after=2, dead_after=None))
    _seed(rt)
    carry = jnp.zeros((rt.n_workers,), jnp.int32)
    body = _body(rt.ops)
    for tick in range(5):
        carry, _ = rt.round(body, carry)
        rt.telemetry.record_request(rid=tick, admit=tick, first=tick + 1,
                                    finish=tick + 2, tokens=4)
        rt.telemetry.record_wave(loads=np.asarray(rt.sizes()), served=1,
                                 tokens=4)
    return rt.telemetry


def test_trace_export_loads_and_validates(tmp_path):
    tele = _traced_telemetry()
    path = tmp_path / "trace.json"
    trace = export_trace(tele, str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["displayTimeUnit"] == "ms"
    counts = validate_trace(on_disk)
    assert counts["round"] == len(tele.rounds)
    assert counts["wave"] == len(tele.waves)
    assert counts["request"] == 3 * len(tele.requests)  # b/n/e per request
    assert counts["fault"] == len(tele.fault_log) >= 1  # the planned kill
    assert validate_trace(trace) == counts


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "X", "pid": 0, "ts": 0.0,
                                         "name": "no-dur"}]})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "b", "pid": 0, "ts": 0.0,
                                         "name": "unmatched", "id": 7,
                                         "cat": "request"}]})


# -- metrics -----------------------------------------------------------------


def test_registry_exposition_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "a counter")
    c.inc(2, lane=0)
    c.inc(3, lane=1)
    reg.gauge("t_gauge", "a gauge").set(1.5)
    h = reg.histogram("t_hist", "a histogram", buckets=(1, 2, 4))
    for v in (0.5, 3, 100):
        h.observe(v)
    text = reg.to_prometheus()
    assert '# TYPE t_total counter' in text
    assert 't_total{lane="1"} 3' in text
    assert 't_hist_bucket{le="+Inf"} 3' in text
    assert "t_hist_count 3" in text
    snap = reg.snapshot()
    assert snap["t_gauge"]["values"] == 1.5
    assert snap["t_hist"]["values"]["count"] == 3
    with pytest.raises(ValueError):
        reg.gauge("t_total", "type clash")


def test_runtime_metrics_cover_rounds_and_detector():
    rt = _make_rt(fault_plan=FaultPlan())
    rt.attach_detector(DetectorPolicy(suspect_after=2))
    _seed(rt)
    _drive(rt, rounds=3, fused=2)
    snap = rt.metrics().snapshot()
    assert snap["repro_rounds_total"]["values"] == 5
    healthy = snap["repro_detector_lanes"]["values"]['{state="healthy"}']
    assert healthy == rt.n_workers
    assert snap["repro_queue_items"]["values"] == int(rt.sizes().sum())
    assert snap["repro_compiled_programs"]["values"] == len(rt._compiled)


def test_write_textfile_atomic(tmp_path):
    reg = MetricsRegistry()
    reg.counter("t_total", "c").inc()
    path = tmp_path / "metrics" / "repro.prom"
    write_textfile(reg, str(path))
    assert path.read_text().rstrip().endswith("t_total 1")
    assert list(path.parent.iterdir()) == [path]  # no tmp litter


def test_run_resilient_writes_metrics_textfile(tmp_path):
    from repro.launch.resilient import run_resilient

    def make_runtime():
        rt = _make_rt()
        _seed(rt, 32)
        return rt

    def drive(rt, should_stop):
        body = _body(rt.ops)
        while rt.total_size() > 0 and not should_stop():
            rt.round(body)
        return rt.rounds_run

    path = tmp_path / "live.prom"
    rounds = run_resilient(make_runtime, drive,
                           snapshot_dir=str(tmp_path / "snap"),
                           metrics_path=str(path), metrics_every_s=0.0)
    assert rounds > 0
    text = path.read_text()
    assert f"repro_rounds_total {rounds}" in text


# -- wall-clock failure detection --------------------------------------------


def test_observe_wall_suspects_but_never_kills_by_default():
    det = FailureDetector(2, DetectorPolicy(
        wall_clock=True, wall_slow_factor=2.0, wall_window=8,
        suspect_after=1, dead_after=2))
    for _ in range(8):
        assert det.observe_wall(0, 1.0) == "healthy"
    assert det.observe_wall(0, 10.0) == "suspected"
    assert det.observe_wall(0, 10.0) == "suspected"  # capped: no kill
    assert det.state(1) == "healthy"                 # per-lane isolation
    det.revive(0)
    assert det.observe_wall(0, 10.0) == "healthy"    # history cleared too


def test_observe_wall_kill_opt_in():
    det = FailureDetector(1, DetectorPolicy(
        wall_clock=True, wall_kill=True, wall_window=8,
        suspect_after=1, dead_after=2))
    killed = []
    det.on_dead = killed.append
    for _ in range(8):
        det.observe_wall(0, 1.0)
    det.observe_wall(0, 10.0)
    assert det.observe_wall(0, 10.0) == "dead"
    assert killed == [0]


def test_runtime_feeds_wall_clock_detector():
    rt = _make_rt(fault_plan=FaultPlan())
    det = rt.attach_detector(DetectorPolicy(wall_clock=True, wall_window=4))
    _seed(rt)
    _drive(rt, rounds=6, fused=2)
    assert all(len(det._wall_hist[w]) > 0 for w in range(rt.n_workers))


# -- PagedQueue spill/refill counters ----------------------------------------


def test_paged_queue_spill_counters():
    spec = jax.ShapeDtypeStruct((), jnp.int32)
    pq = PagedQueue(8, spec, low_watermark=2)
    assert (pq.spills, pq.spilled_items, pq.refills, pq.refilled_items) \
        == (0, 0, 0, 0)
    for base in range(0, 24, 4):
        pq.push(jnp.arange(base, base + 4, dtype=jnp.int32), 4)
    assert pq.spills > 0
    assert pq.spilled_items == sum(n for _, n in pq.pages)
    popped = 0
    while True:
        _, valid = pq.pop()
        if not valid:
            break
        popped += 1
    assert popped == 24
    assert pq.refills > 0 and pq.refilled_items == pq.spilled_items


def test_paged_queue_metrics_collector():
    from repro.obs.metrics import collect_paged_queue

    spec = jax.ShapeDtypeStruct((), jnp.int32)
    pq = PagedQueue(8, spec, low_watermark=2)
    for base in range(0, 16, 4):
        pq.push(jnp.arange(base, base + 4, dtype=jnp.int32), 4)
    snap = collect_paged_queue(MetricsRegistry(), pq).snapshot()
    assert snap["repro_paged_total_items"]["values"] == pq.total_size()
    assert snap["repro_paged_spilled_items_total"]["values"] \
        == pq.spilled_items


# -- trend gating ------------------------------------------------------------


def _bench(tmp_path, name, payload):
    p = tmp_path / name
    p.write_text(json.dumps(payload))
    return str(p)


def test_trend_passes_within_tolerance(tmp_path):
    _bench(tmp_path, "BENCH_PR2.json",
           {"meta": {"bench": "BENCH_PR2"},
            "fig9_device_fused": {"fused_speedup": 5.0}})
    cur = _bench(tmp_path, "BENCH_NEW.json",
                 {"meta": {"bench": "BENCH_PR11"},
                  "fig9_device_fused": {"fused_speedup": 4.5}})
    assert trend.main(["--dir", str(tmp_path), "--current", cur]) == 0


def test_trend_exits_nonzero_on_regression(tmp_path):
    _bench(tmp_path, "BENCH_PR2.json",
           {"meta": {"bench": "BENCH_PR2"},
            "fig9_device_fused": {"fused_speedup": 5.0}})
    cur = _bench(tmp_path, "BENCH_NEW.json",
                 {"meta": {"bench": "BENCH_PR11"},
                  "fig9_device_fused": {"fused_speedup": 1.2}})
    assert trend.main(["--dir", str(tmp_path), "--current", cur]) == 1


def test_trend_bool_gate_and_ceiling(tmp_path):
    bad = _bench(tmp_path, "BENCH_PR10.json",
                 {"meta": {"bench": "BENCH_PR10"},
                  "obs_overhead": {"probe_overhead": 1.2,
                                   "gates_ok": False}})
    assert trend.main(["--dir", str(tmp_path)]) == 1
    os.unlink(bad)
    _bench(tmp_path, "BENCH_PR10.json",
           {"meta": {"bench": "BENCH_PR10"},
            "obs_overhead": {"probe_overhead": 1.01, "gates_ok": True}})
    assert trend.main(["--dir", str(tmp_path)]) == 0


def test_trend_report_artifact(tmp_path):
    _bench(tmp_path, "BENCH_PR5.json",
           {"meta": {"bench": "BENCH_PR5"},
            "fig11_mesh": {"mesh_matches_vmap": True}})
    report = tmp_path / "report.json"
    assert trend.main(["--dir", str(tmp_path),
                       "--report", str(report)]) == 0
    data = json.loads(report.read_text())
    assert data["ok"] is True
    assert data["series"]["mesh_matches_vmap"] == [["BENCH_PR5.json", True]]
