"""Host time per round of the DAG drains spent enqueueing a fused block
(us/round): ``steal:enqueue``'s self time (building the arguments and
dispatching, less any compile) over ``steal.rounds``."""

from bench.program_spans import per_count


def read(ctx):
    return per_count("steal.rounds", 1e6, own=("steal:enqueue",))
