"""The queue kernels' share of their HBM roofline in the UTS searches (%):
the drain's reader, ``queue_kernel_roofline.drain``, on this cell's
counters (24-byte items) and kernel time."""

from bench.harness import load_reader

read = load_reader("queue_kernel_roofline.drain")
