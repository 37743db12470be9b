"""Unified observability: metrics registry + trace spans + HBM accounting.

The three instruments the serving ladder reports through (see
``repro.serve``'s "Observability" section for the scheduler-facing view):

* ``registry`` — ``MetricsRegistry``: counters / gauges / fixed-bucket
  histograms. Always live: the schedulers' ``stats()`` running totals ARE
  registry counters now (the dicts' public shapes are unchanged).
* ``tracer`` — ``SpanTracer``: per-request lifecycle events
  (submit → queue → place → chunk* → evict → complete → poll), JSONL
  export, text timelines, and the zero-span-loss audit
  (``check_complete``).
* ``traffic`` — ``TrafficAccountant``: modeled HBM bytes charged per
  dispatch decision using the ``kernels/ops.py`` dispatch-table formulas,
  plus a roofline bytes-vs-FLOPs summary (``launch/roofline.py``).

``Observability`` bundles the three with one enable switch and one
injected clock. ``enabled=False`` swaps the tracer and accountant for
their null twins — the registry stays live because ``stats()`` depends
on it; counter increments are the part of the overhead budget that is
not optional. The obs-overhead CI job holds the *enabled* path to <= 5%
throughput/p99 overhead over disabled on the scheduler DES.

Per-process aggregation: every ``Observability`` defaults to parenting
its registry and accountant to the process-global bundle
(``get_global()``), mirroring ``ops.dispatch_counters``'s stack idiom —
scheduler-local metrics stay isolated for ``stats()`` while
``benchmarks/run.py`` dumps one ``OBS_<suite>.json`` per suite from the
global and resets it between suites (``reset_global()``). Tracers are
NOT globally merged: rid spaces are per scheduler, so spans live with
their scheduler (``sched.obs.tracer``).

Measured performance
--------------------
The accountant's bytes are *modeled*; two further members carry the
*measured* half (``repro.obs.profile`` / ``repro.obs.measure``):

* ``phases`` — ``PhaseTimer``: scheduler round phases under
  ``profile.phase.<name>`` (total) and ``...<name>.self`` (exclusive of
  nested phases), in seconds. Names: ``serve.{evict,evict.read,admit,
  admit.launch,chunk,upkeep,poll,points}`` and
  ``cluster.{prep,evict,admit,gang,chunk,poll}``. Each phase is also a
  ``jax.profiler.TraceAnnotation`` of its name over the same interval,
  so a profiler trace charges device time and idle time to the phases
  on the device's own clock.
* ``profile`` — ``KernelProfiler``: solve/chunk launches timed per
  measurement cell ``kernel|MxN|s<itemsize>|impl|source|L|T`` (the
  traffic formulas' own parameters), first-call (trace+compile) under
  ``profile.compile.<cell>`` split from steady-state execute under
  ``profile.kernel.<cell>``. The hook is installed around launches via
  ``ops.launch_profiler`` and forces a device sync per timed launch —
  which is why ``enabled=False`` swaps in null twins that install
  nothing. Only the cluster scheduler's sync step mode installs it: the
  ``UOTScheduler`` round no longer times launches (a profiler trace
  gives its chunks' device time), so its profiler stays empty.

``measure.MeasurementStore`` persists a profiler's cells as
fingerprint-stamped JSON (schema in its docstring); dividing each
cell's modeled bytes by its measured seconds yields achieved GB/s and
a **measured** roofline fraction (``store.achieved()``) next to the
accountant's modeled one. Stored cells feed back into serving:
``measure.MeasuredDispatch`` advises ``ops`` ``impl='auto'`` when both
tiers of a cell have data, and ``core.predict.measured_seconds_per_iter``
turns predicted iterations into predicted seconds from measured chunk
cost (both schedulers accept ``measurements=``).

Operational telemetry
---------------------
Every surface above is cumulative-since-start; the *operational plane*
(``attach_operational``) adds the windowed / alerting / incident-capture
layer on top. Four members, each with an ``obs=False`` null twin:

* ``windows`` — ``windows.WindowedAggregator``: ring of cumulative
  registry snapshots on the scheduler's injected clock, ticked once per
  round; ``windows.window(N)`` yields per-window counter deltas/rates,
  gauge last-values, and histogram-delta p50/p90/p99 (total at 0/1
  observations — ``registry.percentile_from_state`` never emits NaN).
* ``slo`` — ``slo.SLOMonitor`` over declarative ``slo.SLO(name,
  objective, window, series)`` objectives, evaluated per round with
  multi-window (fast/slow) burn-rate rules and BrownoutController-style
  hysteresis. Transitions are typed ``slo.Alert`` events routed through
  the registry (``slo.alerts.firing``/``.resolved`` counters,
  ``slo.<name>.burn``/``.firing`` gauges), the span tracer (an
  ``alert`` event under control-plane rid ``-1``), and ``on_alert``
  callbacks.
* ``flight`` — ``flight.FlightRecorder``: bounded black-box ring of
  per-round scheduler state (queue depth, in-flight, occupancy, device
  health) plus lifecycle notes (placements, sheds, faults, requeues).
  Both schedulers wire ``dump_on`` triggers — a firing alert
  (``alert:<slo>``), device ``quarantine``, ``gang_timeout``, and a
  terminal ``request_failure`` — each freezing the ring into a
  replayable JSONL capture (``write_jsonl``/``load_jsonl``/``render``).
* ``exporter`` — ``export.Exporter``: Prometheus text exposition
  (``prometheus()``; validated by ``export.parse_prometheus_text``),
  whole-bundle JSON ``snapshot()``/``delta()``, and the stdlib scrape
  endpoint ``serve_http()`` (``/metrics`` + ``/snapshot.json``).

Metric names the plane adds (joining the schedulers' ``serve.*`` /
``cluster.*`` namespaces):

======================== ==============================================
``slo.alerts.firing``    counter: alert transitions into firing
``slo.alerts.resolved``  counter: alert transitions into resolved
``slo.<name>.burn``      gauge: the SLO's fast-window burn rate
``slo.<name>.firing``    gauge: 0/1 current alert state
======================== ==============================================

Schema crib: an ``Alert`` is ``{name, state: firing|resolved, t, value,
objective, burn_fast, burn_slow, window, fast_window}``; a flight
capture is a JSONL header ``{"flight": {trigger, reason, t, rounds,
meta}}`` followed by one round per line ``{t, step, events: [{kind, t,
...}], queued, in_flight, occupancy, ...}``.
"""
from __future__ import annotations

import time
from typing import Callable

from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                DEFAULT_COUNT_BUCKETS, DEFAULT_TIME_BUCKETS,
                                geometric_buckets, percentile_from_state)
from repro.obs.trace import NullTracer, SpanTracer, TERMINAL_STATUSES
from repro.obs.traffic import (NullAccountant, TrafficAccountant,
                               chunk_bytes, cost_source_bytes,
                               gang_collective_bytes, modeled_flops,
                               solve_bytes)
from repro.obs.profile import (KernelProfiler, NullKernelProfiler,
                               NullPhaseTimer, PhaseTimer, cell_key,
                               parse_cell_key)
from repro.obs.measure import (MeasuredDispatch, MeasurementMismatch,
                               MeasurementStore, machine_fingerprint)
from repro.obs.windows import (NullWindowedAggregator, WindowedAggregator,
                               WindowView)
from repro.obs.slo import (SLO, Alert, CounterDelta, CounterRate,
                           CounterRatio, Drift, GaugeSeries,
                           HistPercentile, NullSLOMonitor, SLOMonitor,
                           Series, default_slos, roofline_drift)
from repro.obs.flight import FlightDump, FlightRecorder, NullFlightRecorder
from repro.obs.export import (Exporter, NullExporter, ObsHTTPServer,
                              parse_prometheus_text, prometheus_text,
                              render_dashboard, serve_http, snapshot_delta)

__all__ = [
    "Observability", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "SpanTracer", "NullTracer", "TrafficAccountant", "NullAccountant",
    "PhaseTimer", "NullPhaseTimer", "KernelProfiler", "NullKernelProfiler",
    "MeasurementStore", "MeasuredDispatch", "MeasurementMismatch",
    "machine_fingerprint", "cell_key", "parse_cell_key",
    "TERMINAL_STATUSES", "DEFAULT_TIME_BUCKETS", "DEFAULT_COUNT_BUCKETS",
    "geometric_buckets", "percentile_from_state", "cost_source_bytes",
    "solve_bytes", "chunk_bytes", "gang_collective_bytes", "modeled_flops",
    "get_global", "reset_global", "global_dump",
    # operational plane (windows / SLO / flight / exporters)
    "WindowedAggregator", "NullWindowedAggregator", "WindowView",
    "SLO", "Alert", "SLOMonitor", "NullSLOMonitor", "Series",
    "CounterRatio", "CounterDelta", "CounterRate", "HistPercentile",
    "GaugeSeries", "Drift", "roofline_drift", "default_slos",
    "FlightRecorder", "NullFlightRecorder", "FlightDump",
    "Exporter", "NullExporter", "ObsHTTPServer", "serve_http",
    "prometheus_text", "parse_prometheus_text", "snapshot_delta",
    "render_dashboard",
]


class Observability:
    """One scheduler's (or one suite's) instrument bundle.

    ``enabled=False`` keeps the registry live (stats' counters must keep
    counting) but swaps tracing and traffic accounting for no-ops.
    ``parent`` defaults to the process-global bundle; pass
    ``parent=None`` explicitly via ``chain=False`` to isolate (tests).
    """

    def __init__(self, *, enabled: bool = True,
                 clock: Callable[[], float] = time.monotonic,
                 chain: bool = True,
                 parent: "Observability | None" = None):
        if parent is None and chain:
            parent = get_global()
        self.enabled = enabled
        self.parent = parent
        self.clock = clock
        self.registry = MetricsRegistry(
            parent=parent.registry if parent is not None else None)
        # operational plane: null until attach_operational() builds it
        # (schedulers attach; the attributes always exist so callers
        # never need hasattr guards)
        self.windows = NullWindowedAggregator()
        self.slo = NullSLOMonitor()
        self.flight = NullFlightRecorder()
        self.exporter = NullExporter()
        if enabled:
            self.tracer = SpanTracer(clock=clock, registry=self.registry)
            self.traffic = TrafficAccountant(
                parent=parent.traffic if parent is not None else None)
            # wall-clock instruments (see "Measured performance" above):
            # these time the HOST, so they run on perf_counter regardless
            # of the scheduler's (possibly simulated) clock
            self.phases = PhaseTimer(self.registry)
            self.profile = KernelProfiler(
                self.registry,
                parent=(parent.profile if parent is not None
                        and parent.profile.enabled else None))
        else:
            self.tracer = NullTracer(clock=clock)
            self.traffic = NullAccountant()
            self.phases = NullPhaseTimer()
            self.profile = NullKernelProfiler()

    def attach_operational(self, *, slos=(), clock=None,
                           max_window: float = 900.0,
                           flight_capacity: int = 256,
                           keep_dumps: int = 8, on_alert=(),
                           window_seconds=(60.0,)) -> "Observability":
        """Build the operational plane (windows + SLO monitor + flight
        recorder + exporter) onto this bundle — see the module
        docstring's "Operational telemetry" section. Under
        ``enabled=False`` the members stay their null twins, so the
        whole plane costs three no-op attribute calls per round.
        ``clock`` defaults to the bundle's own (schedulers pass their
        possibly-simulated clock so windows run in DES seconds)."""
        clock = clock if clock is not None else self.clock
        if self.enabled:
            self.windows = WindowedAggregator(
                self.registry, clock=clock, max_window=max_window)
            self.flight = FlightRecorder(
                capacity=flight_capacity, keep_dumps=keep_dumps,
                clock=clock)
            self.slo = SLOMonitor(
                self.windows, slos, registry=self.registry,
                tracer=self.tracer, clock=clock, on_alert=on_alert)
            self.exporter = Exporter(
                self, windows=self.windows, slo=self.slo,
                flight=self.flight, window_seconds=window_seconds)
        return self

    def dump(self) -> dict:
        """Registry + traffic + profile (+ operational plane, when
        attached) snapshot — the ``OBS_<suite>.json`` payload; spans
        export separately via ``tracer.write_jsonl``."""
        out = {"enabled": self.enabled, "registry": self.registry.dump(),
               "traffic": self.traffic.dump(),
               "profile": self.profile.dump()}
        if self.slo.enabled:
            out["slo"] = self.slo.dump()
        if self.windows.enabled:
            out["windows_samples"] = self.windows.samples
        return out


class _GlobalObservability(Observability):
    """The process-global aggregation root (no parent, no clock user)."""

    def __init__(self):
        super().__init__(enabled=True, chain=False, parent=None)

    def reset(self) -> None:
        self.registry.reset()
        self.traffic.reset()
        self.tracer.clear()
        self.profile.reset()
        self.windows.reset()
        self.slo.reset()
        self.flight.reset()


_GLOBAL: _GlobalObservability | None = None


def get_global() -> _GlobalObservability:
    """The process-global ``Observability`` every child chains to by
    default (created on first use)."""
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = _GlobalObservability()
    return _GLOBAL


def reset_global() -> None:
    """Zero the global registry and accountant (between benchmark suites;
    schedulers built BEFORE a reset keep counting into the old, orphaned
    parent metrics — build them after)."""
    get_global().reset()


def global_dump() -> dict:
    """Snapshot of the process-global bundle."""
    return get_global().dump()
