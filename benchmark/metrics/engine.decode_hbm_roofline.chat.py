"""Decode program: least time to stream weights + live KV at the HBM
peak, over its traced device time."""

from benchmark.readers import decode_hbm_roofline as read  # noqa: F401
