"""Share of the UTS searches' popped nodes that a steal moved first (%):
the items the exchange moved (counter ``steal.items_moved``, recorded
from each round's telemetry while the window is traced) over the nodes
the lanes popped in the window."""

from bench.program_spans import snapshot


def read(ctx):
    snap = snapshot()
    popped = ctx["counters"].get("popped", 0)
    if snap is None or popped <= 0 or "steal.items_moved" not in snap[
            "counters"]:
        return None
    return 100.0 * snap["counters"]["steal.items_moved"] / popped
