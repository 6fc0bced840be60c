"""Median device time of one execution of the decode program, from the
trace's programs line."""

from benchmark.readers import decode_step_ms as read  # noqa: F401
