"""Host milliseconds of a scheduler step (median): `serve/step` less the
`engine/prefill` and `engine/decode` spans under it."""

from benchmark.span_readers import host_ms_per_step as read  # noqa: F401
