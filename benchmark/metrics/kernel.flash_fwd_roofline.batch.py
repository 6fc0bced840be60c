"""Pallas flash attention forward (`%flash_fwd*` in the trace): causal
attention FLOPs of the real prompt tokens over bf16 peak x device time."""

from benchmark.readers import flash_fwd_roofline as read  # noqa: F401
