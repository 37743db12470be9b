"""Reduction of a profiler trace to device busy and idle time.

``load`` keeps, from the ``.xplane.pb`` file that ``jax.profiler`` writes,
the op events of each device and the benchmark's own host spans (names
starting with ``bench.`` or ``serve.``), as plain lists
``[name, start_ns, duration_ns]``. ``reduce`` turns those into:

- ``window_s``: the traced window, from the ``bench.window`` span;
- ``busy_s``: the union of the intervals in which an op ran, per device,
  averaged over the devices;
- ``ops``: seconds per op under a stable name (the HLO name without its
  number, so ``fusion.12`` and ``fusion.7`` are one ``fusion``), averaged
  over the devices, most first; ops that contain others (``while``) are
  left out;
- ``idle_gaps``: idle seconds inside the window, summed by the innermost
  host span open while the device was idle (``host_idle`` where none is);
- ``busy_in``: busy seconds inside the spans of each name.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os
import re

HOST_PREFIXES = ("bench.", "serve.")
OPS_LINE = "XLA Ops"        # the line of a device plane that holds its ops
WINDOW_SPAN = "bench.window"
# ops that hold other ops of the same line; kept for busy time, left out
# of the per-op seconds
CONTAINERS = ("while",)


def options():
    """Profiler options: host spans, no Python function tracing."""
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:(TPU|GPU):\d+", name) is not None


def load(path: str) -> dict:
    """The device op events and host spans of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if is_device_plane(plane.name):
            devices[plane.name] = [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_PREFIXES)]
    return {"devices": devices, "host": host}


def stable_name(op: str) -> str:
    """``fusion`` of ``%fusion.12 = f32[...] fusion(...)``: the op's HLO
    name without its leading ``%``, its number and its text."""
    return re.sub(r"\.\d+$", "", op.split(" = ", 1)[0].lstrip("%"))


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """``[lo, hi]`` cut into segments, each labelled with the innermost
    (latest opened) host span open over it, ``host_idle`` where none is."""
    bounds = sorted([(s, 1, n, s) for n, s, e in spans]
                    + [(e, 0, n, s) for n, s, e in spans])
    heap, closed, out, t = [], set(), [], lo
    for x, opening, name, start in bounds + [(hi, 0, None, None)]:
        x = min(max(x, lo), hi)
        while heap and (-heap[0][0], heap[0][1]) in closed:
            heapq.heappop(heap)
        if x > t:
            out.append((t, x, heap[0][1] if heap else "host_idle"))
            t = x
        if name is None:
            break
        if opening:
            heapq.heappush(heap, (-start, name))
        else:
            closed.add((start, name))
    return out


def label_overlap(intervals, segments) -> collections.Counter:
    """Length of the merged ``intervals`` inside each label's segments."""
    out = collections.Counter()
    i = j = 0
    while i < len(intervals) and j < len(segments):
        lo = max(intervals[i][0], segments[j][0])
        hi = min(intervals[i][1], segments[j][1])
        if hi > lo:
            out[segments[j][2]] += hi - lo
        if intervals[i][1] < segments[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(events: dict) -> dict | None:
    """Busy, idle and per-op seconds of a loaded trace; None without a
    window span or without device events."""
    host = [(n, s, s + d) for n, s, d in events["host"]]
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    devices = {k: v for k, v in events["devices"].items() if v}
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    spans = [h for h in host if h[0] != WINDOW_SPAN]
    by_name = collections.defaultdict(list)
    for n, s, e in spans:
        by_name[n].append((s, e))
    by_name = {n: merge(v) for n, v in by_name.items()}
    segments = innermost(spans, lo, hi)

    nd = len(devices)
    busy_ns = 0.0
    ops = collections.Counter()
    idle = collections.Counter()
    busy_in = collections.Counter()
    for evs in devices.values():
        ivs = [(s, s + d) for _, s, d in evs]
        busy = merge(clip(ivs, lo, hi))
        busy_ns += length(busy)
        for name, s, d in evs:
            if stable_name(name) not in CONTAINERS:
                ops[stable_name(name)] += length(clip([(s, s + d)], lo, hi))
        idle.update(label_overlap(gaps(busy, lo, hi), segments))
        for n, ivs_n in by_name.items():
            busy_in[n] += overlap(busy, ivs_n)

    def top(counter):
        return [[k, v / nd / 1e9] for k, v in counter.most_common(10)]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / nd / 1e9,
        "devices": nd,
        "ops": top(ops),
        "idle_gaps": top(idle),
        "busy_in": {k: v / nd / 1e9 for k, v in busy_in.items()},
    }
