"""Share of the costed drains' popped nodes that a steal moved first (%):
the UTS searches' reader, ``stolen_share.uts``, on this cell's counter
``steal.items_moved`` and popped nodes."""

from bench.harness import load_reader

read = load_reader("stolen_share.uts")
