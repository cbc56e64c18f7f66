"""repro.obs — observability for the steal runtime.

Four cooperating pieces (DESIGN.md §11):

* :mod:`repro.obs.spans` — host spans and counters of the runtime's
  dispatch loop and per-call set-up, recorded only while a
  ``jax.profiler`` session records, on the profiler's clock.
* :mod:`repro.obs.trace` — Chrome-trace/Perfetto JSON export of one
  :class:`~repro.runtime.telemetry.Telemetry` stream: round spans, wave
  spans, per-request flows, fault/detector instant events on one
  timeline.
* :mod:`repro.obs.metrics` — counter/gauge/histogram registry with
  Prometheus text exposition and JSON snapshots, fed by the telemetry,
  the failure detector, both admission masters and PagedQueue spill
  accounting.
* ``benchmarks/trend.py`` (outside the package, next to the BENCH
  history it reads) — perf-trend gating over the checked-in
  ``BENCH_*.json`` series.
"""

from repro.obs.metrics import (MetricsRegistry, master_metrics,  # noqa: F401
                               runtime_metrics)
from repro.obs.trace import export_trace, validate_trace  # noqa: F401

__all__ = ["MetricsRegistry", "runtime_metrics", "master_metrics",
           "export_trace", "validate_trace"]
