"""Per-call set-up of the knapsack solves (ms/solve): building and
seeding the runtime (``solve:build``), tracing and compiling or loading
its program (``steal:compile``) and reading the answer back
(``solve:result``), over ``solve.calls``."""

from bench.program_spans import per_count


def read(ctx):
    return per_count("solve.calls", 1e3,
                     total=("solve:build", "steal:compile", "solve:result"))
