"""Pallas latent decode attention (`%mla_decode_fwd*`): least time to read
one cached latent row a live context position at the HBM peak, over its
device time."""

from benchmark.ling_readers import mla_decode_roofline as read  # noqa: F401
