"""`device_idle_pct.small`: 1 - device busy / traced window, closed-loop service cell."""
from bench.layers import idle_pct as read  # noqa: F401
