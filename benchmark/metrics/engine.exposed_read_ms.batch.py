"""Milliseconds a 1,024-bucket prefill call's host reads wait beyond the
program (median): its summed `engine/read` spans less
`jit_serve_prefill_1024`'s median device time in the traced window."""

from benchmark.call_readers import exposed_read_ms_prefill as read  # noqa: F401
