"""Whole serving step's share of the chip's bf16 peak: model FLOPs
(benchmark/flops_falcon_h1.py: the state-space mixer as its recurrence, the
attention over each token's live context) of every prompt token prefilled and
every token decoded inside the window, over window x peak."""

from benchmark.readers import serve_mfu as read  # noqa: F401
