"""Milliseconds a decode step spends publishing (median): `serve/publish`:
gauges, TTFT attribution, the registry's per-step fetch."""

from benchmark.span_readers import publish_ms_per_step as read  # noqa: F401
