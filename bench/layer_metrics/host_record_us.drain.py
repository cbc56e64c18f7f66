"""Host time per round of the DAG drains spent recording a fused block
(us/round): ``steal:record`` (per-round telemetry, controller, detector,
snapshot cadence) over ``steal.rounds``."""

from bench.program_spans import per_count


def read(ctx):
    return per_count("steal.rounds", 1e6, total=("steal:record",))
