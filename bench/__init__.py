"""The on-chip benchmark of the steal runtime (see ``harness.py``)."""
