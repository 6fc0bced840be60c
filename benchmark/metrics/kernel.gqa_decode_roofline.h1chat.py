"""Pallas paged decode attention under grouped-query heads
(`%paged_decode_fwd*`): least time to read the live K and V rows once a KV
head at the HBM peak, over its device time."""

from benchmark.falcon_h1_readers import gqa_decode_roofline as read  # noqa: F401
