"""Host spans and counters of the steal runtime, on the profiler's clock.

The runtime's host loop (enqueue a fused block, wait for it, read its
telemetry back, record it) and its per-call set-up (build a runtime,
trace and compile its program) are where the chip sits idle; this module
names those steps.  Recording follows the profiler: while no
``jax.profiler`` session is recording, :func:`span` and :func:`count`
check one flag and return, so untraced runs record nothing and pay a
fraction of a microsecond per call.  While a session records:

* each :func:`span` opens a ``jax.profiler.TraceAnnotation`` of the same
  name, so it appears in the trace's host plane on the timeline the
  device planes share;
* it is appended as ``(name, start_ns, end_ns, parent)`` to an in-memory
  buffer of :data:`CAPACITY` spans (a full buffer counts what it drops
  under ``spans.dropped``), stamped with :func:`time.time_ns`, the clock
  the profiler's host events carry, so the buffer can be joined to the
  trace's device gaps offline;
* per-name totals are kept whatever the buffer holds: calls, inclusive
  seconds, and self seconds (the span's duration less the part its
  children cover).

:func:`snapshot` returns the totals and the counters; :func:`reset`
clears them.  Like the profiler session it follows, the record belongs
to the process: spans nest per thread, and the totals, the buffer and
the counters are shared under one lock.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["CAPACITY", "span", "count", "snapshot", "recorded", "reset"]

CAPACITY = 65_536

_recording = TraceAnnotation.is_enabled
_NULL = contextlib.nullcontext()
_buffer: List[Tuple[str, int, int, Optional[str]]] = []
_totals: Dict[str, List[int]] = {}  # name -> [calls, total_ns, self_ns]
_counters: Dict[str, int] = {}
_lock = threading.Lock()
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "annotation", "parent", "children_ns", "start_ns")

    def __init__(self, name: str):
        self.name = name
        self.annotation = TraceAnnotation(name)

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        self.children_ns = 0
        stack.append(self)
        self.annotation.__enter__()
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end_ns = time.time_ns()
        self.annotation.__exit__(*exc)
        _stack().pop()
        duration = end_ns - self.start_ns
        parent = self.parent
        if parent is not None:
            parent.children_ns += duration
        with _lock:
            agg = _totals.get(self.name)
            if agg is None:
                agg = _totals[self.name] = [0, 0, 0]
            agg[0] += 1
            agg[1] += duration
            agg[2] += duration - self.children_ns
            if len(_buffer) < CAPACITY:
                _buffer.append((self.name, self.start_ns, end_ns,
                                parent.name if parent is not None else None))
            else:
                _counters["spans.dropped"] = (
                    _counters.get("spans.dropped", 0) + 1)
        return False


def span(name: str):
    """A context manager that records one span named ``name`` while a
    profiler session records, and does nothing otherwise."""
    if not _recording():
        return _NULL
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while a profiler session records."""
    if _recording():
        with _lock:
            _counters[name] = _counters.get(name, 0) + int(n)


def snapshot() -> dict:
    """``{"spans": {name: {"n", "total_s", "self_s"}}, "counters":
    {name: n}}`` of everything recorded since the last :func:`reset`."""
    with _lock:
        return {"spans": {name: {"n": n, "total_s": total * 1e-9,
                                 "self_s": own * 1e-9}
                          for name, (n, total, own) in _totals.items()},
                "counters": dict(_counters)}


def recorded() -> List[Tuple[str, int, int, Optional[str]]]:
    """The buffered spans in the order they closed: ``(name, start_ns,
    end_ns, parent name or None)`` on :func:`time.time_ns`'s clock."""
    with _lock:
        return list(_buffer)


def reset() -> None:
    """Clear the buffer, the totals and the counters."""
    with _lock:
        _buffer.clear()
        _totals.clear()
        _counters.clear()
