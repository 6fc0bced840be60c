"""Pallas KDA decode step (`%kda_step_fwd*` in the trace): least time to
read and write every rider's f32 state at the HBM peak, over its device
time."""

from benchmark.ling_readers import kda_step_roofline as read  # noqa: F401
