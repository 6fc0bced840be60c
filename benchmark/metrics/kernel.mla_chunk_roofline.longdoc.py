"""Pallas latent-attention chunk kernel (`%mla_chunk_fwd*`): the chunk's
scores and values counted in the un-absorbed form whatever form implements
them, over the kernel's device time and the bf16 peak."""

from benchmark.glm_readers import mla_chunk_roofline as read  # noqa: F401
