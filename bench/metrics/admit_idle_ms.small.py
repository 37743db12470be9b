"""`admit_idle_ms.small`: device idle ms per scheduler round in `serve.admit`, its launches excluded, closed loop."""
from bench.phases import admit as read  # noqa: F401
