"""Host milliseconds of one admission that ran a prefill (median):
`serve/admit` less the `engine/prefill` under it."""

from benchmark.span_readers import admit_host_ms as read  # noqa: F401
