"""Share of lane-rounds of the DAG drains that began with an empty queue
(%), counted on the device by the worker body."""

from bench.readers import starved_percent as read  # noqa: F401
