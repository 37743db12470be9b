"""`solve_roofline`: least HBM bytes of the solve over device busy time in the solve spans, as a share of 819 GB/s."""
from bench.layers import solve_roofline as read  # noqa: F401
