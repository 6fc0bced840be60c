"""Milliseconds the decode call's host reads wait beyond the program
(median): its summed `engine/read` spans less `jit_serve_decode`'s median
device time in the traced window."""

from benchmark.call_readers import exposed_read_ms_decode as read  # noqa: F401
