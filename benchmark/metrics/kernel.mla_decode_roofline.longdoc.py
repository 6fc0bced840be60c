"""Pallas latent decode attention (`%mla_decode_fwd*`) at 8k-25k contexts:
least time to read one cached latent row (640 lanes of bf16) a live context
position a layer at the HBM peak, over its device time."""

from benchmark.glm_readers import mla_decode_roofline as read  # noqa: F401
