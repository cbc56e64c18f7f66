"""Chrome-trace / Perfetto export of one :class:`Telemetry` stream.

One telemetry object already carries the whole story of a run on a
single logical-round timeline: the master's rounds, the workload's
waves, every served request's admit -> first-token -> finish stamps,
and the round-stamped fault/detector event log.  This module renders
that stream as standard `Trace Event Format`_ JSON — load the file in
``chrome://tracing``, https://ui.perfetto.dev or ``about:tracing`` and
the rounds, waves, requests and failures line up on one zoomable
timeline.

The clock is LOGICAL: one round occupies ``round_us`` microseconds of
trace time (default 1000 us = 1 ms per round), so traces from host,
vmap and mesh runs of the same schedule align event-for-event and are
directly diffable.  Where a round's wall-clock goes is not on this
clock: the host side is in :mod:`repro.obs.spans`, the device side in
the named scopes of a ``jax.profiler`` trace (DESIGN.md §11).

Emitted events (all standard phases, no extensions):

* ``X`` complete spans, pid 0 / tid 0: one ``round N`` per
  :class:`RoundRecord` (args carry steals, items moved, proportion,
  imbalance).
* ``X`` spans, pid 0 / tid 1: one ``wave N`` per :class:`WaveRecord`,
  spanning from the previous wave's closing round to its own (args:
  served, tokens, loads, SLO percentiles).
* ``b``/``n``/``e`` async events, pid 0 / tid 2, one series per
  request id: ``admit -> first_token -> finish`` (args: tokens, ttft
  and latency in rounds).
* ``i`` instant events, pid 0 / tid 0, one per :attr:`Telemetry.
  fault_log` entry — ``kill`` / ``revive`` / ``suspect`` /
  ``auto_kill`` / ``evict`` / ``straggler`` / ... at the round the
  event was recorded, lane-attributed in ``args``.

.. _Trace Event Format:
   https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU
"""

from __future__ import annotations

import argparse
import json
from typing import Any, Dict, List, Optional

from repro.runtime.telemetry import Telemetry

__all__ = ["export_trace", "validate_trace"]

_PID = 0
_TID_ROUNDS = 0
_TID_WAVES = 1
_TID_REQUESTS = 2


def _meta(name: str, tid: int, label: str) -> Dict[str, Any]:
    return {"ph": "M", "pid": _PID, "tid": tid, "name": name,
            "args": {"name": label}}


def _round_event(rec, round_us: float) -> Dict[str, Any]:
    return {
        "ph": "X", "pid": _PID, "tid": _TID_ROUNDS,
        "ts": rec.round * round_us,
        "dur": round_us, "name": f"round {rec.round}", "cat": "round",
        "args": {
            "n_steals": rec.n_steals,
            "n_transferred": rec.n_transferred,
            "bytes_moved": rec.bytes_moved,
            "proportion": rec.proportion,
            "imbalance": rec.imbalance,
            "sizes_total": rec.sizes_total,
        },
    }


def _wave_events(telemetry: Telemetry, round_us: float
                 ) -> List[Dict[str, Any]]:
    events = []
    prev_round = 0
    for w in telemetry.waves:
        # A wave recorded before round alignment existed (round == -1)
        # still renders: pin it one round wide at its index.
        end = w.round if w.round >= 0 else prev_round + 1
        start = min(prev_round, end)
        dur = max(end - start, 1) * round_us
        args = {"served": w.served, "tokens": w.tokens,
                "loads": list(w.loads), "evicted": w.evicted,
                "stragglers": w.stragglers, "migrated": w.migrated}
        if w.latency_p50 or w.ttft_p50:
            args.update(ttft_p50=w.ttft_p50, ttft_p95=w.ttft_p95,
                        latency_p50=w.latency_p50, latency_p95=w.latency_p95)
        events.append({
            "ph": "X", "pid": _PID, "tid": _TID_WAVES, "ts": start * round_us,
            "dur": dur, "name": f"wave {w.wave}", "cat": "wave",
            "args": args,
        })
        prev_round = end
    return events


def _request_events(telemetry: Telemetry, round_us: float
                    ) -> List[Dict[str, Any]]:
    events = []
    for r in telemetry.requests:
        name = f"request {r.rid}"
        common = {"pid": _PID, "tid": _TID_REQUESTS, "cat": "request",
                  "id": r.rid, "name": name}
        events.append({**common, "ph": "b", "ts": r.admit * round_us,
                       "args": {"tokens": r.tokens}})
        events.append({**common, "ph": "n", "ts": r.first * round_us,
                       "name": "first_token",
                       "args": {"ttft_rounds": r.ttft}})
        events.append({**common, "ph": "e", "ts": r.finish * round_us,
                       "args": {"latency_rounds": r.latency,
                                "tokens": r.tokens}})
    return events


def _fault_events(telemetry: Telemetry, round_us: float
                  ) -> List[Dict[str, Any]]:
    events = []
    for kind, lane, rnd in telemetry.fault_log:
        args: Dict[str, Any] = {"round": rnd}
        if lane >= 0:
            args["lane"] = lane
        events.append({
            "ph": "i", "pid": _PID, "tid": _TID_ROUNDS, "ts": rnd * round_us,
            "s": "p", "name": kind, "cat": "fault", "args": args,
        })
    return events


def export_trace(telemetry: Telemetry, path: Optional[str] = None, *,
                 round_us: float = 1000.0) -> Dict[str, Any]:
    """Render ``telemetry`` as a Chrome-trace dict (and write it as JSON
    when ``path`` is given).  ``round_us`` sets the logical clock: trace
    microseconds per round."""
    events: List[Dict[str, Any]] = [
        _meta("process_name", _TID_ROUNDS, "steal-runtime"),
        _meta("thread_name", _TID_ROUNDS, "rounds"),
        _meta("thread_name", _TID_WAVES, "waves"),
        _meta("thread_name", _TID_REQUESTS, "requests"),
    ]
    events.extend(_round_event(rec, round_us) for rec in telemetry.rounds)
    events.extend(_wave_events(telemetry, round_us))
    events.extend(_request_events(telemetry, round_us))
    events.extend(_fault_events(telemetry, round_us))
    trace = {"traceEvents": events, "displayTimeUnit": "ms",
             "otherData": {"clock": f"logical ({round_us} us per round)",
                           "summary": telemetry.summary()}}
    if path is not None:
        with open(path, "w") as f:
            json.dump(trace, f)
    return trace


def validate_trace(trace: Dict[str, Any]) -> Dict[str, int]:
    """Structural check that ``trace`` is loadable Chrome-trace JSON:
    a ``traceEvents`` list whose entries all carry the mandatory
    ``ph``/``pid``/``ts`` fields (metadata events excepted for ``ts``),
    with matched async begin/end per request id.  Returns per-category
    event counts; raises ``ValueError`` on any violation — this is what
    the CI obs lane runs against the smoke trace."""
    if not isinstance(trace.get("traceEvents"), list):
        raise ValueError("trace has no traceEvents list")
    counts: Dict[str, int] = {}
    async_open: Dict[int, int] = {}
    for i, ev in enumerate(trace["traceEvents"]):
        for field in ("ph", "pid", "name"):
            if field not in ev:
                raise ValueError(f"event {i} missing {field!r}: {ev}")
        ph = ev["ph"]
        if ph not in ("M", "X", "b", "n", "e", "i"):
            raise ValueError(f"event {i} has unexpected phase {ph!r}")
        if ph != "M" and "ts" not in ev:
            raise ValueError(f"event {i} ({ph!r}) missing ts")
        if ph == "X" and ev.get("dur", -1.0) < 0:
            raise ValueError(f"event {i} X span missing/negative dur")
        if ph in ("b", "n", "e") and "id" not in ev:
            raise ValueError(f"event {i} async event missing id")
        if ph == "b":
            async_open[ev["id"]] = async_open.get(ev["id"], 0) + 1
        elif ph == "e":
            open_n = async_open.get(ev["id"], 0)
            if open_n <= 0:
                raise ValueError(f"event {i} ends async id {ev['id']} "
                                 f"with no open begin")
            async_open[ev["id"]] = open_n - 1
        counts[ev.get("cat", ph)] = counts.get(ev.get("cat", ph), 0) + 1
    dangling = {k: v for k, v in async_open.items() if v}
    if dangling:
        raise ValueError(f"unclosed async request events: {dangling}")
    return counts


# ---------------------------------------------------------------------------
# Smoke driver: a tiny seeded chaos drain + serve waves, one stream
# ---------------------------------------------------------------------------


def _smoke_telemetry() -> Telemetry:
    """A deterministic miniature of the full story in one telemetry
    stream: a 4-lane chaos drain (scheduled straggler window the
    detector converts into suspects, a scheduled kill, a live revive)
    with serve-style wave + request records layered on the same round
    clock."""
    import jax
    import jax.numpy as jnp

    from repro.core.policy import StealPolicy
    from repro.runtime.detector import DetectorPolicy
    from repro.runtime.executor import StealRuntime
    from repro.runtime.resilience import FaultPlan

    W, cap, items = 4, 64, 48
    rt = StealRuntime(
        W, cap, {"x": jax.ShapeDtypeStruct((), jnp.int32)},
        policy=StealPolicy(low_watermark=1, high_watermark=4),
        # Lane 1 straggles rounds 2..5 (-> detector suspects), lane 3
        # dies at round 6 (-> recovery superstep drains its ring).
        fault_plan=FaultPlan(kills=((3, 6),), delays=((1, 2, 3),)))
    rt.attach_detector(DetectorPolicy(suspect_after=2, dead_after=None))
    # All work starts on lane 0: the drain IS the rebalance.
    rt.push(0, {"x": jnp.arange(items, dtype=jnp.int32)}, items)

    def body(q, carry):
        q, _, n = rt.ops.pop_bulk(q, 4, jnp.int32(2))
        return q, carry + n.astype(jnp.int32)

    admitted: List[int] = []
    for tick in range(6):
        rt.round(body)               # one round per dispatch
        rt.run_fused(2, body)        # two rounds in one dispatch
        if tick == 2:
            rt.revive_lane(3)
        # Serve layer on the same stream: admit one request per tick,
        # finish it two ticks later (stamps in logical rounds).
        admitted.append(rt.rounds_run)
        if tick >= 2:
            admit = admitted[tick - 2]
            rt.telemetry.record_request(rid=tick - 2, admit=admit,
                                        first=admit + 1,
                                        finish=rt.rounds_run, tokens=8)
        rt.telemetry.record_wave(loads=rt.sizes(), served=1 if tick >= 2
                                 else 0, tokens=8 if tick >= 2 else 0)
    return rt.telemetry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Export a Chrome trace from the repro steal runtime")
    ap.add_argument("--smoke", action="store_true",
                    help="run the built-in seeded chaos+serve drain and "
                         "export its trace")
    ap.add_argument("--out", default="trace.json",
                    help="output path (default trace.json)")
    ap.add_argument("--round-us", type=float, default=1000.0,
                    help="trace microseconds per logical round")
    args = ap.parse_args(argv)
    if not args.smoke:
        ap.error("only --smoke mode is runnable from the CLI; library "
                 "users call export_trace(telemetry, path)")
    tele = _smoke_telemetry()
    trace = export_trace(tele, args.out, round_us=args.round_us)
    counts = validate_trace(trace)
    print(f"wrote {args.out}: " + ", ".join(
        f"{v} {k}" for k, v in sorted(counts.items())))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
