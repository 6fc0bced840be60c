"""Whole serving step's share of the chip's bf16 peak: model FLOPs
(benchmark/flops_glm.py, attention in the un-absorbed form) of every prompt
token a chunk prefilled and every token decoded inside the window, over
window x peak."""

from benchmark.readers import serve_mfu as read  # noqa: F401
