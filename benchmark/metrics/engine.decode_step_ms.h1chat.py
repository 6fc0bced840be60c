"""Median device time of one execution of the decode program over the
iterations it runs (the configuration's `decode_block`): milliseconds a
token step, from the trace's programs line."""

from benchmark.falcon_h1_readers import decode_step_ms as read  # noqa: F401
