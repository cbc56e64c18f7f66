"""The bytes the queue kernels' work needs, counted from the run.

Each item a queue op moves is read once and written once, so it needs
``2 * item_bytes``.  Per op kind:

* ``pop``: the items popped by the worker bodies (ring -> batch);
* ``ring_write``: the items pushed by the worker bodies and the seeding
  pushes (batch -> ring), and the items the thieves splice in (gathered
  window -> ring).  The trace cannot tell a push from a splice: both
  kernels return the ring;
* ``window``: the items the victims hand to the exchange (ring ->
  window).

Only the kinds whose kernel events the trace holds are counted, so the
bytes match the kernel time they are divided by.  What the ring layout
moves beyond this (whole tiles of a lane-sparse ``(rows, 1)`` array,
the unused rows of a ``max_steal`` window) is waste, not need.
"""

from __future__ import annotations

from typing import Iterable

KINDS = ("pop", "ring_write", "window")


def queue_kernel_bytes(counters: dict, kinds: Iterable[str]) -> int:
    items = {"pop": counters["popped"],
             "ring_write": counters["pushed"] + counters["transferred"],
             "window": counters["transferred"]}
    kinds = set(kinds)
    unknown = kinds - set(KINDS)
    if unknown:
        raise ValueError(f"unknown queue op kinds {sorted(unknown)}")
    return sum(2 * int(counters["item_bytes"]) * int(items[k])
               for k in KINDS if k in kinds)
