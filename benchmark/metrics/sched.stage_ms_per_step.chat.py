"""Milliseconds a decode step spends building its batch and staging the
call (median): `serve/batch` plus the decode's `engine/stage`."""

from benchmark.span_readers import stage_ms_per_step as read  # noqa: F401
