"""`evict_idle_ms.small`: device idle ms per scheduler round in `serve.evict`, its lane read-backs excluded, closed loop."""
from bench.phases import evict as read  # noqa: F401
