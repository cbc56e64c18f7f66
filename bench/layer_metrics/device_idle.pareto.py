"""Device 0's idle share of the traced window of costed drains (%), from
the device trace: 1 - (union of its operation intervals) / window."""

from bench.readers import idle_percent as read  # noqa: F401
