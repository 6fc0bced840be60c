"""Milliseconds of a prefill call's launch (median): its `engine/dispatch`,
the compiled call that takes the packed vector as its one transfer."""

from benchmark.call_readers import dispatch_ms_prefill as read  # noqa: F401
