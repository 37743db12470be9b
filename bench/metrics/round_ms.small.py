"""`round_ms.small`: host wall time of the scheduler's step() calls over their count, closed loop."""
from bench.layers import round_ms as read  # noqa: F401
