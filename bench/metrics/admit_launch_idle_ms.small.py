"""`admit_launch_idle_ms.small`: device idle ms per scheduler round in `serve.admit.launch`, transfers and lane admission, closed loop."""
from bench.phases import admit_launch as read  # noqa: F401
