"""Host time per round of the DAG drains spent waiting for a fused block
to finish on the device (us/round): ``steal:wait`` over
``steal.rounds``."""

from bench.program_spans import per_count


def read(ctx):
    return per_count("steal.rounds", 1e6, total=("steal:wait",))
