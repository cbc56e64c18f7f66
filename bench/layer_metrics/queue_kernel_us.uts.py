"""Device 0's time in the queue kernels' custom calls per round of the
UTS searches (us/round), from the device trace: the drain's reader,
``queue_kernel_us.drain``, on this cell's 24-byte rows."""

from bench.harness import load_reader

read = load_reader("queue_kernel_us.drain")
