"""Device idle time charged to the scheduler's round phases.

The ``UOTScheduler``'s phases are profiler annotations (``serve.evict``,
``serve.evict.read``, ``serve.admit``, ``serve.admit.launch``,
``serve.chunk``, ``serve.upkeep``, and ``serve.points`` on the submit
path). ``bench.trace.reduce`` charges each idle gap of the device to the
innermost span open over it, so a phase's label holds its own idle
time, its nested phases' excluded. A program without the annotations
gives these readers nothing to read.
"""
from __future__ import annotations


def idle_ms_per_round(run, span: str) -> float | None:
    """Milliseconds of device idle charged to ``span``, per scheduler
    round; None without a trace summary, rounds or such a label."""
    s = run.trace_summary
    steps = run.facts.get("steps")
    if not s or not steps:
        return None
    idle = dict(s["idle_gaps"]).get(span)
    if idle is None:
        return None
    return 1000.0 * idle / steps


def _reader(span: str):
    def read(run) -> float | None:
        return idle_ms_per_round(run, span)
    read.__doc__ = f"Device idle ms per round charged to ``{span}``."
    return read


evict = _reader("serve.evict")
evict_read = _reader("serve.evict.read")
admit = _reader("serve.admit")
admit_launch = _reader("serve.admit.launch")
chunk = _reader("serve.chunk")
upkeep = _reader("serve.upkeep")
points = _reader("serve.points")
