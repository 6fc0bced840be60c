"""Pallas prompt recurrence of the chunked state-space form
(`%ssd_chunk_fwd*`): its operations over the real prompt tokens (the carried-in
state's read-out and update, each real chunk) against the bf16 peak, over its
device time; compute bounds it (its operands need not cross HBM)."""

from benchmark.falcon_h1_readers import ssd_chunk_roofline as read  # noqa: F401
