"""Host time per round of the UTS searches spent reading a fused block's
telemetry back (us/round): the drain's reader, ``host_readback_us.drain``,
on this cell's spans."""

from bench.harness import load_reader

read = load_reader("host_readback_us.drain")
