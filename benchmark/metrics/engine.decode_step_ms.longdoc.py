"""Median device time of one execution of the decode program (one token
step of the 32 slots), from the trace's programs line."""

from benchmark.glm_readers import decode_step_ms as read  # noqa: F401
