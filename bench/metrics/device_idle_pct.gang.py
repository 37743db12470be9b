"""`device_idle_pct.gang`: 1 - device busy / traced window, averaged over the gang's devices."""
from bench.layers import idle_pct as read  # noqa: F401
