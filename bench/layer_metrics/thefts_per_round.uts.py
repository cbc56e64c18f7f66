"""Steals the master planned per round of the UTS searches
(steals/round): counter ``steal.thefts`` over ``steal.rounds``, both
recorded from each round's telemetry while the window is traced."""

from bench.program_spans import snapshot


def read(ctx):
    del ctx
    snap = snapshot()
    if snap is None:
        return None
    counters = snap["counters"]
    if counters.get("steal.rounds", 0) <= 0 or "steal.thefts" not in counters:
        return None
    return counters["steal.thefts"] / counters["steal.rounds"]
