"""What the per-layer readers in ``layer_metrics/`` share.

A reader takes the run's context ``{"trace", "counters", "peaks",
"chips"}`` (``trace`` is ``trace_reduce.reduce``'s result) and returns
a number, or ``None`` where it finds nothing to read."""

from __future__ import annotations

from typing import Optional

from bench.kernel_bytes import KINDS
from bench.trace_reduce import QUEUE_KERNEL

KNOWN = {QUEUE_KERNEL + kind for kind in KINDS}


def first_device(ctx: dict) -> Optional[dict]:
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    return trace["devices"][min(trace["devices"], key=int)]


def idle_percent(ctx: dict) -> Optional[float]:
    """Device 0's idle share of the traced window, in percent."""
    dev = first_device(ctx)
    if dev is None or ctx["trace"]["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / ctx["trace"]["window_s"])


def queue_kernel_kinds(ctx: dict) -> dict:
    """Device 0's queue-kernel self time by op kind, for the kinds the
    trace holds events of (kernels of no known kind are left out)."""
    dev = first_device(ctx)
    if dev is None:
        return {}
    return {cls[len(QUEUE_KERNEL):]: sec
            for cls, sec in dev["class_s"].items()
            if cls.startswith(QUEUE_KERNEL) and cls in KNOWN}


def per_round_us(seconds: float, ctx: dict) -> Optional[float]:
    rounds = ctx["counters"].get("rounds", 0)
    if rounds <= 0:
        return None
    return 1e6 * seconds / rounds


def starved_percent(ctx: dict) -> Optional[float]:
    c = ctx["counters"]
    if not c.get("lane_rounds"):
        return None
    return 100.0 * c["starved_lane_rounds"] / c["lane_rounds"]
