"""Whole serving step's share of the chip's bf16 peak: model FLOPs
(benchmark/flops_ling.py: this chip's share of the stack, the routed experts
at their expected load) of every prompt token prefilled and every token
decoded inside the window, over window x peak."""

from benchmark.readers import serve_mfu as read  # noqa: F401
