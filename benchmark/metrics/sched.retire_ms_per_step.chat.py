"""Milliseconds a decode step spends after the engine returns (median):
`serve/retire`: tokens appended, counters, finished requests retired."""

from benchmark.span_readers import retire_ms_per_step as read  # noqa: F401
