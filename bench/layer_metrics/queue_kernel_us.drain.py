"""Device 0's time in the queue kernels' custom calls per round of the
DAG drains (us/round), from the device trace."""

from bench.readers import per_round_us, queue_kernel_kinds


def read(ctx):
    kinds = queue_kernel_kinds(ctx)
    if not kinds:
        return None
    return per_round_us(sum(kinds.values()), ctx)
