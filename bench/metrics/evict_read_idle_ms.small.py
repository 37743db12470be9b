"""`evict_read_idle_ms.small`: device idle ms per scheduler round in `serve.evict.read`, each evicted lane's read-back, closed loop."""
from bench.phases import evict_read as read  # noqa: F401
