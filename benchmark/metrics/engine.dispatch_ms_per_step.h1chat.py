"""Milliseconds a scheduler step spends launching compiled calls (median):
every `engine/dispatch` of the step summed (the lazy prefills' under their
`engine/stage`, the decode block's under `engine/decode`)."""

from benchmark.call_readers import dispatch_ms_per_step as read  # noqa: F401
