"""Programs compiled or loaded from the persistent cache per knapsack
solve, from JAX's own ``backend_compile_duration`` monitoring events."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("solves"):
        return None
    return c["compiles"] / c["solves"]
