"""Host time per round of the UTS searches spent waiting for a fused block
to finish on the device (us/round): the drain's reader,
``host_wait_us.drain``, on this cell's spans."""

from bench.harness import load_reader

read = load_reader("host_wait_us.drain")
