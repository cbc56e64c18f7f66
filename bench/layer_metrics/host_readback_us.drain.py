"""Host time per round of the DAG drains spent reading a fused block's
telemetry back (us/round): ``steal:readback`` over ``steal.rounds``."""

from bench.program_spans import per_count


def read(ctx):
    return per_count("steal.rounds", 1e6, total=("steal:readback",))
