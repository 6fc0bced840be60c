"""Chunk prefill programs (`jit_serve_chunk_prefill_*`): model FLOPs of the
real tokens of the traced chunks (each query against the keys before it)
over the programs' traced device time and the bf16 peak."""

from benchmark.glm_readers import chunk_prefill_mfu as read  # noqa: F401
