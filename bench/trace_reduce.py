"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

What a TPU trace holds, as read by hand from one: a plane per chip
(``/device:TPU:<i>``) whose ``XLA Ops`` line has one event per executed
HLO instruction, named by the instruction's whole text (``%copy.3 =
s32[16384,1]{...} copy(...)``); control flow (``while``,
``conditional``) is an event that encloses the events of its body.  The
host plane has a line per thread; the Python thread's holds the
benchmark's own ``bench:*`` annotations and JAX's dispatch events.

Two things the names do not give, and how they are recovered:

* The Pallas queue kernels are custom calls (``custom_call_target=
  "tpu_custom_call"``) but carry no kernel name inside the fused
  program.  Their output shape tells them apart: a pop returns the
  popped batch (``pop_batch`` rows), a push or a thief's splice returns
  the ring (``ring_slots`` rows), the victim's window returns
  ``max_steal`` rows.  The caller passes that map (``kernel_rows``).
* Time by instruction is inclusive of enclosed events; the reduction
  keeps self time (an event's duration less that of the events nested
  in it on the same line).

The reduction takes the window from the ``bench:window`` annotation;
per chip it takes busy time (the union of operation intervals inside the
window), self time by instruction (keyed by its name without the
numeric suffix, its opcode and its output shape) and by class (the
queue kernels' kinds, ``collective``); and it lists chip 0's idle gaps,
each labelled by the innermost event of the annotating host thread that
covers the gap's midpoint.  The result is plain data, so the readers in
``layer_metrics/`` and the tests work on it without the profiler.
"""

from __future__ import annotations

import gzip
import heapq
import re
from typing import Dict, List, Tuple

WINDOW = "bench:window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("all-gather", "all-gather-start", "all-reduce",
               "all-reduce-start", "all-to-all", "collective-permute",
               "collective-permute-start", "reduce-scatter")
QUEUE_KERNEL = "queue_kernel."

_OPCODE = re.compile(r"[\]\})] ([a-z][a-z0-9\-]*)\(")
_ROWS = re.compile(r"^\(?[a-z0-9]+\[(\d+)")
_SUFFIX = re.compile(r"(\.\d+)+$")

Interval = Tuple[float, float]
Event = Tuple[str, float, float]


def describe(text: str, kernel_rows: Dict[int, str]) -> Tuple[str, List[str]]:
    """An instruction's short key (``name opcode shape``) and classes."""
    name, _, rest = text.partition(" = ")
    shape = rest.split(" ", 1)[0]
    found = _OPCODE.search(rest)
    opcode = found.group(1) if found else "?"
    classes = []
    if KERNEL_TARGET in rest:
        rows = _ROWS.match(shape)
        kind = kernel_rows.get(int(rows.group(1))) if rows else None
        classes.append(QUEUE_KERNEL + (kind or "other"))
    if opcode in COLLECTIVES:
        classes.append("collective")
    key = f"{_SUFFIX.sub('', name.lstrip('%'))} {opcode} {shape}"
    return key, classes


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events: List[Event]) -> List[Event]:
    """Each event with its duration less that of the events nested in it
    (events of one line nest or are disjoint)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack: List[Tuple[float, int]] = []
    for i in order:
        _, start, dur = events[i]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur
        stack.append((start + dur, i))
    return [(e[0], e[1], own[i]) for i, e in enumerate(events)]


def labels(host: List[Event], points: List[float]) -> List[str]:
    """For each time in ``points``, the innermost (shortest) host event
    that covers it, by a sweep over events sorted by start."""
    events = sorted((s, s + d, name) for name, s, d in host
                    if name != WINDOW)
    order = sorted(range(len(points)), key=lambda i: points[i])
    out, active, j = [WINDOW] * len(points), [], 0
    for i in order:
        t = points[i]
        while j < len(events) and events[j][0] <= t:
            s, e, name = events[j]
            heapq.heappush(active, (e, e - s, name))
            j += 1
        while active and active[0][0] < t:
            heapq.heappop(active)
        if active:
            out[i] = min(active, key=lambda a: a[1])[2]
    return out


def load(path: str):
    """The trace file as ``ProfileData`` (``.xplane.pb``, or gzipped)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def extract(path: str, kernel_rows: Dict[int, str]) -> dict:
    """Read the trace into plain data: the window, each TPU chip's
    operation events (short key, start, duration, classes) and the
    events of the host thread that holds the window annotation.  Times
    are in nanoseconds."""
    window, host, devices = None, [], {}
    seen: Dict[str, Tuple[str, List[str]]] = {}
    for plane in load(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.name not in seen:
                        seen[ev.name] = describe(ev.name, kernel_rows)
                    key, classes = seen[ev.name]
                    events.append((key, ev.start_ns, ev.duration_ns,
                                   classes))
            devices[plane.name[len(DEVICE_PREFIX):]] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, ev.start_ns, ev.duration_ns)
                          for ev in line.events]
                marks = [e for e in events if e[0] == WINDOW]
                if marks and window is None:
                    window = [marks[0][1], marks[0][1] + marks[0][2]]
                    host = events
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane")
    return {"window_ns": window, "devices": devices, "host": host}


def reduce(raw: dict, chips: int) -> dict:
    """The numbers the readers take, from :func:`extract`'s data, in
    seconds: ``window_s``; ``busy_s`` (averaged over the first ``chips``
    chips); per chip ``busy_s``, ``op_s`` (self time by instruction
    key), ``class_s`` and ``class_n`` (self time and event count by
    class); and chip 0's idle ``gaps`` as ``[label, seconds]``, longest
    first."""
    lo, hi = raw["window_ns"]
    per_device = {}
    for key in sorted(raw["devices"], key=int)[:chips]:
        # Each event cut to the window (one the window cuts counts only
        # its part inside).
        events = [(k, max(s, lo), min(s + d, hi) - max(s, lo), c)
                  for k, s, d, c in raw["devices"][key]
                  if s + d > lo and s < hi]
        busy = union([(s, s + d) for _, s, d, _ in events])
        own = self_times([(k, s, d) for k, s, d, _ in events])
        op_s: Dict[str, float] = {}
        class_s: Dict[str, float] = {}
        class_n: Dict[str, int] = {}
        for (k, _, own_ns), (_, _, _, classes) in zip(own, events):
            op_s[k] = op_s.get(k, 0.0) + own_ns * 1e-9
            for cls in classes:
                class_s[cls] = class_s.get(cls, 0.0) + own_ns * 1e-9
                class_n[cls] = class_n.get(cls, 0) + 1
        per_device[key] = {"busy_s": sum(e - s for s, e in busy) * 1e-9,
                           "op_s": op_s, "class_s": class_s,
                           "class_n": class_n, "busy": busy}
    if len(per_device) < chips:
        raise ValueError(f"the trace holds {len(per_device)} TPU chips, "
                         f"the cell uses {chips}")
    first = per_device[min(per_device, key=int)]
    holes = gaps(first["busy"], lo, hi)
    names = labels(raw["host"], [(s + e) / 2 for s, e in holes])
    idle = sorted(([n, (e - s) * 1e-9] for n, (s, e) in zip(names, holes)),
                  key=lambda x: -x[1])
    for dev in per_device.values():
        del dev["busy"]
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(d["busy_s"] for d in per_device.values())
            / len(per_device),
            "devices": per_device, "gaps": idle}


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: chip 0's instructions by self
    time, and its idle time summed by what the host was doing."""
    first = reduced["devices"][min(reduced["devices"], key=int)]
    ops = sorted(first["op_s"].items(), key=lambda x: -x[1])[:top]
    by_label: Dict[str, float] = {}
    for label, sec in reduced["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + sec
    idle = sorted(by_label.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
