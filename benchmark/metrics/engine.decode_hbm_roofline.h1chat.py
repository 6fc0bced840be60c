"""Least time to move what a decode program must (the bf16 weights once an
iteration, the riders' f32 state and bf16 convolution tails in and out, the
live bf16 K/V rows once a KV head) at the HBM peak, over the decode program's
device time."""

from benchmark.falcon_h1_readers import decode_hbm_roofline as read  # noqa: F401
