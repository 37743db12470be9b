"""What every run shares: the run's context handed to a driver, host
spans, the count of programs compiled, and the device facts."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import statistics
import sys
import time

import jax

from bench import trace as tracing

# Fired once for every program JAX builds for a device, whether compiled
# or loaded from the persistent compilation cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A cell and the files ``BENCHMARK.json`` names it by."""
    entry: dict          # the cell's entry of BENCHMARK.json
    workload: dict       # bench/workloads/<cell>.json
    config: dict         # bench/configs/<config>.json
    traffic: dict | None  # bench/traffic/<mix>.json
    driver: object       # bench/drivers/<driver>.py

    def new_run(self, *, seed: int, seconds: float, trace: bool, devices,
                t_start: float, config: dict | None = None,
                traffic: dict | None = None) -> "Run":
        """A run of the cell; ``config`` or ``traffic`` replace the
        cell's own (for the control and the load sweeps)."""
        return Run(name=self.entry["name"], cell=self.entry,
                   workload=self.workload, config=config or self.config,
                   traffic=traffic or self.traffic, seed=seed,
                   seconds=seconds, trace=trace, devices=devices,
                   t_start=t_start)


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``; KeyError if absent."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    entry = cells[name]
    bdir = root / "bench"
    workload = load_json(bdir / "workloads" / f"{name}.json")
    mix = bdir / "traffic" / f"{entry['traffic']}.json"
    return Cell(entry=entry, workload=workload,
                config=load_json(bdir / "configs" / f"{entry['config']}.json"),
                traffic=load_json(mix) if mix.exists() else None,
                driver=load_module(bdir / "drivers"
                                   / f"{workload['driver']}.py"))


def devices_for(cell: dict, platform: str) -> list | None:
    """JAX's devices, or None (with the reason on standard error) where
    they are not of ``platform`` or fewer than the cell's chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        eprint(f"bench: JAX platform is {devices[0].platform!r}, not "
               f"{platform!r}; no result")
        return None
    if len(devices) < cell["chips"]:
        eprint(f"bench: cell {cell['name']} needs {cell['chips']} devices, "
               f"found {len(devices)}; no result")
        return None
    return devices


class Spans:
    """Host spans ``(name, start_s, end_s)`` on ``time.perf_counter``.

    With ``annotate`` each span is also a ``jax.profiler.TraceAnnotation``,
    so it appears in the device trace on the trace's own clock.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> tuple[int, float]:
        """(count, seconds) of the spans called ``name``."""
        ds = [t1 - t0 for n, t0, t1 in self.records if n == name]
        return len(ds), sum(ds)


class CompileCounter:
    """Counts the programs JAX builds while ``active`` is set."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1


class GcClock:
    """Counts Python's garbage collections, and the seconds they take,
    while ``active`` is set."""

    def __init__(self):
        self.active = False
        self.counts = [0, 0, 0]
        self.seconds = 0.0
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            if self.active:
                self.counts[info["generation"]] += 1
                self.seconds += time.perf_counter() - self._t0
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


@dataclasses.dataclass
class Run:
    """One run of one cell, as its module in ``bench/drivers/`` sees it.

    That module builds the inputs, warms up, brackets the measured window
    with ``start_window`` and ``end_window``, frees the program's state
    and compares what the window produced with the reference. It reports
    through ``metrics`` (end-to-end values), ``checks`` (each number
    compared, with its limit), ``facts`` (what the per-layer readers need)
    and ``lines`` (earlier lines of the output).
    """
    name: str
    cell: dict           # the cell's entry of BENCHMARK.json
    workload: dict       # bench/workloads/<cell>.json
    config: dict         # bench/configs/<config>.json
    traffic: dict | None  # bench/traffic/<mix>.json
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float       # perf_counter at process start
    trace_dir: str | None = None
    trace_summary: dict | None = None   # bench.trace.reduce of the window
    spans: Spans = None
    compiles: CompileCounter = None
    gc_clock: GcClock = None
    setup_s: float | None = None
    window_start: float | None = None
    memory_peak_bytes: int = 0
    attempted: int = 0
    failed: int = 0
    metrics: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    facts: dict = dataclasses.field(default_factory=dict)
    lines: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        self.spans = Spans(annotate=self.trace)
        self.compiles = CompileCounter()
        self.gc_clock = GcClock()

    @property
    def used_devices(self) -> list:
        return self.devices[:self.cell["chips"]]

    def start_window(self) -> float:
        """Open the measured window; everything before it is set-up."""
        if self.trace:
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=tracing.options())
            self._window_span = jax.profiler.TraceAnnotation(
                tracing.WINDOW_SPAN)
            self._window_span.__enter__()
        self.compiles.active = True
        self.gc_clock.active = True
        t = self.window_start = time.perf_counter()
        self.setup_s = t - self.t_start
        parts = {n: self.spans.total(n)[1] for n in ("bench.data",
                                                      "bench.warmup")}
        self.note(f"set-up {self.setup_s!r} s: data {parts['bench.data']!r}"
                  f" s, warm-up {parts['bench.warmup']!r} s, the rest "
                  f"(start, imports, devices) "
                  f"{self.setup_s - sum(parts.values())!r} s")
        return t

    def end_window(self) -> float:
        """Close the window, read the device memory peak, stop the trace.

        Call it once the last timed result is ready on the device and
        before the reference runs, which would otherwise set the peak.
        """
        t = time.perf_counter()
        self.compiles.active = False
        self.gc_clock.active = False
        self.gc_clock.close()
        self.memory_peak_bytes = memory_peak_bytes(self.used_devices)
        if self.trace:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.note(f"compiles inside the window: {self.compiles.count}")
        g = self.gc_clock
        self.note(f"garbage collections inside the window: generation 0, "
                  f"1, 2: {g.counts}, {g.seconds!r} s in all")
        return t

    def note_spread(self, what: str, name: str) -> None:
        """One line on the window's spans called ``name``: how many, their
        least, median and largest, when the largest began, and the seconds
        spent beyond the median, which says whether a slow window was slow
        throughout or stalled a few times."""
        spans = [(t1 - t0, t0) for n, t0, t1 in self.spans.records
                 if n == name and t0 >= self.window_start]
        if not spans:
            self.note(f"{what}: none")
            return
        ds = [d for d, _ in spans]
        med = statistics.median(ds)
        longest, began = max(spans)
        self.note(f"{what}: {len(ds)}, least {min(ds)!r} s, median {med!r} "
                  f"s, largest {longest!r} s from {began - self.t_start!r} s "
                  f"after the process began, beyond the median "
                  f"{sum(d - med for d in ds if d > med)!r} s in all")

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    def note(self, line: str) -> None:
        self.lines.append(line)
        print(line, flush=True)

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim for _, v, lim in self.checks)


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def device_facts(run: Run) -> dict:
    d = run.devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(run.used_devices),
            "memory_peak_bytes": run.memory_peak_bytes}


def eprint(*args) -> None:
    print(*args, file=sys.stderr, flush=True)
