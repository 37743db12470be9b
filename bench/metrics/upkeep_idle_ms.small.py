"""`upkeep_idle_ms.small`: device idle ms per scheduler round in `serve.upkeep`, occupancy snapshot and operational plane, closed loop."""
from bench.phases import upkeep as read  # noqa: F401
