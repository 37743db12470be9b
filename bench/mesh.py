"""The four-chip gang cell's arithmetic: its least bytes per device, and
the exposed share of its all-reduce, read from the profiler trace.

``bench/trace.py`` averages over the devices and keeps no op intervals
per device plane, so the collective's share is read here, from the same
``.xplane.pb`` file, once the window's trace is on disk.
"""
from __future__ import annotations

import re

from bench import bytecount, trace

# The program's span around each gang launch and its wait (the name the
# program gives it; a program without it gives this reader nothing).
GANG_SPAN = "gang.solve"
COLLECTIVES = ("all-reduce",)


def least_bytes_per_device(M: int, N: int, devices: int, itemsize: int,
                           iters: int) -> int:
    """One read of every element of a device's ``(M / devices, N)`` row
    block of the coupling per iteration, whatever schedule runs."""
    return bytecount.least_solve_bytes(M // devices, N, itemsize, iters)


def load(path: str) -> dict:
    """Each device plane's op events and the host spans the reader needs
    (``gang.solve`` and the window), as ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if trace.is_device_plane(plane.name):
            devices[plane.name] = [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == trace.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name in (GANG_SPAN, trace.WINDOW_SPAN)]
    return {"devices": devices, "host": host}


def is_collective(op: str) -> bool:
    """An all-reduce, by its HLO opcode: the op's name is the primitive's
    (``%psum.21 = f32[1,8192]{...} all-reduce(...)`` on a v5e), so the
    opcode is read from the text after the shape."""
    head, _, text = op.partition(" = ")
    return (head.lstrip("%").startswith(COLLECTIVES)
            or re.search(r" (%s)(-start|-done)?\(" % "|".join(COLLECTIVES),
                         text) is not None)


def subtract(a, b) -> list[tuple[float, float]]:
    """The merged intervals ``a`` without the merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def exposed_collective_pct(events: dict) -> float | None:
    """The largest share, over the devices, of the time inside the
    ``gang.solve`` spans in which a collective runs on the device and no
    other op does; None without such spans or without device events."""
    spans = trace.merge((s, s + d) for n, s, d in events["host"]
                        if n == GANG_SPAN)
    inside = trace.length(spans)
    if not inside or not any(events["devices"].values()):
        return None
    shares = []
    for evs in events["devices"].values():
        coll = trace.merge((s, s + d) for n, s, d in evs if is_collective(n))
        other = trace.merge(
            (s, s + d) for n, s, d in evs if not is_collective(n)
            and trace.stable_name(n) not in trace.CONTAINERS)
        shares.append(trace.overlap(subtract(coll, other), spans) / inside)
    return 100.0 * max(shares)


def collective_exposed_pct(run) -> float | None:
    return run.facts.get("collective_exposed_pct")
