"""`points_idle_ms.small`: device idle ms per scheduler round in `serve.points`, point-cloud submission, closed loop."""
from bench.phases import points as read  # noqa: F401
