"""Least time to move what a decode step must (unrouted weights, the
experts the step touched by the program's counter, the riders' recurrent
state in and out, the live latent rows) at the HBM peak, over the decode
program's device time."""

from benchmark.ling_readers import decode_hbm_roofline as read  # noqa: F401
