"""Share of lane-rounds of the knapsack solves that began with an empty
queue (%), from the per-round queue sizes the runtime returns."""

from bench.readers import starved_percent as read  # noqa: F401
