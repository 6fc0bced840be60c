"""Pallas state-space decode step (`%ssm_step_fwd*` in the trace): least
time to read and write every rider's f32 state at the HBM peak, over its
device time."""

from benchmark.falcon_h1_readers import ssm_step_roofline as read  # noqa: F401
