"""Share of lane-rounds of the costed drains that began with an empty
queue and a free slot (%), counted on the device by the worker body."""

from bench.readers import starved_percent as read  # noqa: F401
