"""Host time per round of the UTS searches spent enqueueing a fused block
(us/round): the drain's reader, ``host_enqueue_us.drain``, on this cell's
spans."""

from bench.harness import load_reader

read = load_reader("host_enqueue_us.drain")
