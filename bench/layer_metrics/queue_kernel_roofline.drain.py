"""The queue kernels' share of their HBM roofline in the DAG drains (%):
the bytes their work needs (``kernel_bytes``) over kernel time times the
chip's peak HBM bandwidth.  The kernels move little data and no
arithmetic to speak of, so bandwidth is the bound.  Device 0's kernel
time is set against device 0's share of the bytes: all of them on one
chip, one chip's share where the lanes are spread over ``chips``."""

from bench.kernel_bytes import queue_kernel_bytes
from bench.readers import queue_kernel_kinds


def read(ctx):
    kinds = queue_kernel_kinds(ctx)
    seconds = sum(kinds.values())
    if not kinds or seconds <= 0 or not ctx.get("peaks"):
        return None
    need = queue_kernel_bytes(ctx["counters"], kinds) / ctx["chips"]
    return 100.0 * need / (seconds * ctx["peaks"]["hbm_bytes_per_s"])
