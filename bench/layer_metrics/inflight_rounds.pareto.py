"""Share of the costed drains' rounds that began with every queue empty
(%): rounds that only work in flight ran, the straggler tail, counted
from the runtime telemetry's per-round queue sizes."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("rounds") or "inflight_only_rounds" not in c:
        return None
    return 100.0 * c["inflight_only_rounds"] / c["rounds"]
