"""`chunk_idle_ms.small`: device idle ms per scheduler round in `serve.chunk`, the chunk launches, closed loop."""
from bench.phases import chunk as read  # noqa: F401
