"""`device_idle_pct.solve`: 1 - device busy / traced window, one-shot solve cells."""
from bench.layers import idle_pct as read  # noqa: F401
