"""Prefill programs: model FLOPs of the real prompt tokens (not bucket
padding) over their traced device time and the bf16 peak."""

from benchmark.readers import prefill_mfu as read  # noqa: F401
