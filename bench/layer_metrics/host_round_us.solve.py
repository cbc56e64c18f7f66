"""Host time per superstep of the knapsack solves outside the device wait
(us/round): ``steal:enqueue``'s self time (less the compile),
``steal:readback`` and ``steal:record``, over ``steal.rounds``."""

from bench.program_spans import per_count


def read(ctx):
    return per_count("steal.rounds", 1e6,
                     total=("steal:readback", "steal:record"),
                     own=("steal:enqueue",))
