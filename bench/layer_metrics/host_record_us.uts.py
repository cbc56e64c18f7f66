"""Host time per round of the UTS searches spent recording a fused block
(us/round): the drain's reader, ``host_record_us.drain``, on this cell's
spans."""

from bench.harness import load_reader

read = load_reader("host_record_us.drain")
