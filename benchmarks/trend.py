"""Perf-trend gating over the checked-in ``BENCH_*.json`` history.

Every PR's benchmark lane writes a ``BENCH_PR<n>.json`` with its own
schema (figure tables, sweep cells, gate booleans).  This module is the
one place that knows how to read ALL of them: an extractor registry maps
each canonical metric to the JSON path that carries it, normalizing the
per-PR schemas into one series per metric ordered by PR.  The gate then
compares the newest point of each series against the median of its
history:

* **numeric** metrics regress when the newest point is worse than the
  median baseline by more than the metric's tolerance (direction-aware:
  ``fused_speedup`` must not drop, overhead ratios must not climb) or
  breaches the metric's absolute ceiling (e.g. the phase probe's
  hard < 1.05x budget);
* **boolean** gates (exchange-payload flatness, vmap/mesh parity, serve
  parity, the observability gate bundle) must simply be true in the
  newest file that reports them.

A metric with fewer than two points has no trend to judge — it reports
``n/a`` and only its ceiling (if any) applies.  Missing history files
are skipped silently: the registry deliberately tolerates partial
checkouts (CI-artifact-only benches like BENCH_PR9 are judged only on
runs that produce them).

CLI::

  PYTHONPATH=src python -m benchmarks.trend [--dir .] \
      [--current BENCH_NEW.json ...] [--report trend_report.json]

``--current`` appends freshly-produced files as the newest points;
without it, the newest checked-in file per metric is judged.  Exits 1
on any regression.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import re
import statistics
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["METRICS", "MetricSpec", "load_series", "evaluate", "main"]


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One canonical metric and how to judge it.

    Attributes:
      path: key path into a BENCH file's JSON (tuple of dict keys).
      kind: ``"higher"`` / ``"lower"`` (numeric, direction of good) or
        ``"bool"`` (must be true).
      tolerance: allowed relative slack vs the median baseline before a
        numeric point counts as a regression (0.25 = 25 %).
      ceiling: optional absolute bound a "lower" metric must stay under
        (checked even with no history).
      floor: optional absolute bound a "higher" metric must stay over.
    """

    path: Tuple[str, ...]
    kind: str = "higher"
    tolerance: float = 0.25
    ceiling: Optional[float] = None
    floor: Optional[float] = None


# One entry per metric the repo's history carries; the BENCH schemas are
# per-PR, so the paths below are the single normalization point.
METRICS: Dict[str, MetricSpec] = {
    # BENCH_PR2: Fig. 9 fused-dispatch speedup and Fig. 6 kernel latency
    # flatness (1 -> 1024 batch growth factor; flat = close to 1).
    "fused_speedup": MetricSpec(("fig9_device_fused", "fused_speedup"),
                                kind="higher", tolerance=0.35, floor=1.0),
    "push_flatness": MetricSpec(("fig6_push", "kernel_flatness_1_to_1024"),
                                kind="lower", tolerance=0.75),
    # BENCH_PR4 / PR5 / PR8: structural gates.
    "payload_ratio_equals_w": MetricSpec(
        ("fig10_scaling", "payload_ratio_equals_w"), kind="bool"),
    "mesh_matches_vmap": MetricSpec(("fig11_mesh", "mesh_matches_vmap"),
                                    kind="bool"),
    "serve_parity": MetricSpec(("serve_decode", "parity", "parity_ok"),
                               kind="bool"),
    "balanced_beats_rr": MetricSpec(("serve_decode", "balanced_beats_rr"),
                                    kind="bool"),
    # BENCH_PR9 (CI-artifact-only): armed-idle fault-layer overhead.
    "chaos_armed_overhead": MetricSpec(
        ("chaos_recovery", "armed_overhead", "armed flat", "overhead"),
        kind="lower", tolerance=0.5, ceiling=2.0),
    # BENCH_PR10 schema: the retired phase probe's overhead budget +
    # gate bundle (no such file is checked in any more; ROADMAP Design 7).
    "obs_probe_overhead": MetricSpec(("obs_overhead", "probe_overhead"),
                                     kind="lower", tolerance=0.5,
                                     ceiling=1.05),
    "obs_gates_ok": MetricSpec(("obs_overhead", "gates_ok"), kind="bool"),
}


def _dig(data: Dict, path: Tuple[str, ...]) -> Any:
    cur: Any = data
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return cur


def _pr_order(path: str, data: Dict) -> Tuple[int, str]:
    name = str(data.get("meta", {}).get("bench", os.path.basename(path)))
    m = re.search(r"PR(\d+)", name)
    return (int(m.group(1)) if m else 10**6, os.path.basename(path))


def load_series(history: Sequence[str], current: Sequence[str] = ()
                ) -> Dict[str, List[Tuple[str, Any]]]:
    """Normalize BENCH files into ``{metric: [(source, value), ...]}``,
    history ordered by PR number, then the ``current`` files (in the
    given order) as the newest points.  Unreadable files are skipped
    with a warning on stderr; files that don't carry a metric simply
    contribute no point to it."""
    loaded: List[Tuple[str, Dict]] = []
    for path in history:
        try:
            with open(path) as f:
                loaded.append((path, json.load(f)))
        except (OSError, json.JSONDecodeError) as e:
            print(f"[trend] skipping unreadable {path}: {e}",
                  file=sys.stderr)
    loaded.sort(key=lambda pd: _pr_order(*pd))
    for path in current:  # newest points, caller-given order preserved
        with open(path) as f:
            loaded.append((path, json.load(f)))
    series: Dict[str, List[Tuple[str, Any]]] = {m: [] for m in METRICS}
    for path, data in loaded:
        for name, spec in METRICS.items():
            value = _dig(data, spec.path)
            if value is not None:
                series[name].append((os.path.basename(path), value))
    return {m: pts for m, pts in series.items() if pts}


def evaluate(series: Dict[str, List[Tuple[str, Any]]]
             ) -> List[Dict[str, Any]]:
    """Judge every metric's newest point; returns one verdict row per
    metric (``ok`` bool + human-readable ``detail``)."""
    rows: List[Dict[str, Any]] = []
    for name, points in series.items():
        spec = METRICS[name]
        source, value = points[-1]
        row: Dict[str, Any] = {"metric": name, "source": source,
                               "value": value, "n_points": len(points),
                               "kind": spec.kind}
        if spec.kind == "bool":
            row["ok"] = bool(value)
            row["detail"] = "true" if value else "GATE FALSE"
            rows.append(row)
            continue
        value = float(value)
        ok, details = True, []
        if spec.ceiling is not None and value > spec.ceiling:
            ok = False
            details.append(f"{value:.3f} > ceiling {spec.ceiling:g}")
        if spec.floor is not None and value < spec.floor:
            ok = False
            details.append(f"{value:.3f} < floor {spec.floor:g}")
        history = [float(v) for _, v in points[:-1]]
        if history:
            baseline = statistics.median(history)
            row["baseline"] = baseline
            if spec.kind == "higher":
                limit = baseline * (1.0 - spec.tolerance)
                if value < limit:
                    ok = False
                    details.append(
                        f"{value:.3f} < {limit:.3f} "
                        f"(median {baseline:.3f} - {spec.tolerance:.0%})")
            else:
                limit = baseline * (1.0 + spec.tolerance)
                if value > limit:
                    ok = False
                    details.append(
                        f"{value:.3f} > {limit:.3f} "
                        f"(median {baseline:.3f} + {spec.tolerance:.0%})")
        else:
            details.append("no history (first point)")
        row["ok"] = ok
        row["detail"] = "; ".join(details) if details else "within tolerance"
        rows.append(row)
    return rows


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Gate benchmark trends over the BENCH_*.json history")
    ap.add_argument("--dir", default=".",
                    help="directory holding the checked-in BENCH_*.json "
                         "history (default: cwd)")
    ap.add_argument("--current", nargs="*", default=[],
                    help="freshly-produced BENCH files to judge as the "
                         "newest points (appended after the history)")
    ap.add_argument("--report", default=None,
                    help="write the normalized series + verdicts here "
                         "as JSON (the CI artifact)")
    args = ap.parse_args(argv)

    current = [os.path.abspath(p) for p in args.current]
    history = sorted(
        p for p in glob.glob(os.path.join(args.dir, "BENCH_*.json"))
        if os.path.abspath(p) not in current)
    if not history and not current:
        print(f"[trend] no BENCH_*.json under {args.dir!r} and no "
              f"--current files; nothing to gate", file=sys.stderr)
        return 2
    series = load_series(history, current)
    rows = evaluate(series)

    width = max(len(r["metric"]) for r in rows)
    regressed = [r for r in rows if not r["ok"]]
    for r in rows:
        mark = "ok " if r["ok"] else "REG"
        val = f"{r['value']:.3f}" if r["kind"] != "bool" else str(r["value"])
        print(f"[trend] {mark} {r['metric']:<{width}} {val:>8} "
              f"({r['n_points']} pts, {r['source']}) — {r['detail']}")
    if args.report:
        with open(args.report, "w") as f:
            json.dump({"series": {m: [[s, v] for s, v in pts]
                                  for m, pts in series.items()},
                       "verdicts": rows,
                       "ok": not regressed}, f, indent=1)
        print(f"[trend] report -> {args.report}")
    if regressed:
        print(f"[trend] {len(regressed)} metric(s) regressed: "
              + ", ".join(r["metric"] for r in regressed), file=sys.stderr)
        return 1
    print(f"[trend] all {len(rows)} gated metrics within tolerance")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
