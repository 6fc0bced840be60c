"""Pallas paged decode attention (`%paged_decode_fwd*` in the trace): least
time to read the live K and V at the HBM peak, over its device time."""

from benchmark.readers import paged_decode_roofline as read  # noqa: F401
