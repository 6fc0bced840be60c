"""Pallas grouped expert matmul (`%moe_grouped_fwd*`): least time to stream
the experts each call touched (the program's counter) and its routed rows
at the HBM peak, over its device time."""

from benchmark.ling_readers import moe_grouped_roofline as read  # noqa: F401
