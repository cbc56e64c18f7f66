"""What the readers of the ``program_span`` metrics share: the host spans
and counters that ``repro.obs.spans`` records while the profiler traces
the window.  A program without that module, or one that recorded none
of the spans a metric reads, gives ``None``."""

from __future__ import annotations

from typing import Iterable, Optional


def snapshot() -> Optional[dict]:
    try:
        from repro.obs import spans
    except ImportError:
        return None
    return spans.snapshot()


def per_count(counter: str, scale: float, total: Iterable[str] = (),
              own: Iterable[str] = ()) -> Optional[float]:
    """``scale`` times the seconds inside the spans ``total`` (whole) and
    ``own`` (less their children), over the counter ``counter``."""
    snap = snapshot()
    if snap is None:
        return None
    total, own = tuple(total), tuple(own)
    found = snap["spans"]
    n = snap["counters"].get(counter, 0)
    if n <= 0 or not any(name in found for name in total + own):
        return None
    seconds = (sum(found[name]["total_s"] for name in total if name in found)
               + sum(found[name]["self_s"] for name in own if name in found))
    return scale * seconds / n
