"""Observability overhead: the scheduler DES with obs on vs off.

Replays the same Poisson trace (the ``bench_serve`` recipe: measured
wall-clock service times advance a simulated clock) through two
``UOTScheduler`` configurations:

  * **off** — ``obs=False``: the metrics registry stays live (``stats()``
    counters are not optional), but the span tracer and the HBM-traffic
    accountant are their null twins;
  * **on**  — the default bundle: every lifecycle event traced, every
    dispatch decision charged — PLUS the full operational telemetry
    plane (PR 10): per-round window ticks over the whole registry, SLO
    burn-rate evaluation against declared objectives, and the black-box
    flight recorder closing a round capture every step. The <= 5% bar
    covers all of it.

Because the DES folds each ``step()``'s measured host time into the
simulated clock, the *simulated* throughput and p99 absorb the obs
layer's real host cost — which is exactly the quantity the acceptance
bar bounds. Each mode runs ``REPEATS`` times after a shared compile
warmup and keeps its best (min makespan / min p99) replay, so scheduler
jitter does not masquerade as obs overhead.

The *on* mode is the full default bundle — which since the measured-
performance layer includes the wall-clock profiler hooks (``PhaseTimer``
round phases + the ``ops.launch_profiler`` kernel timer with its
per-launch device sync), so the <= 5% bar covers profiling too, not just
tracing and byte accounting.

Hard-asserts (the obs-overhead CI job): on-vs-off overhead <= 5% on both
throughput (makespan) and p99 latency. ``BENCH_OBS_SMOKE=1`` shrinks the
trace for CI — at smoke scale the p99 of a 16-request trace is a
max-statistic over ~ms latencies (one noisy chunk anywhere swamps a 5%
bar without any obs involvement), so the smoke run repeats more and
holds p99 to a jitter-tolerant bar while keeping the full 5% bar on
throughput; the strict p99 bar belongs to the full-size run. The smoke
p99 bar is 1.5x since the operational plane landed: a registry-wide
window tick every ``op_interval`` rounds folds ~10us/round of host time
into the simulated clock, which is invisible against full-size ~50ms
latencies but a real ~0.15x on a 16-request smoke p99 of ~6ms (and the
max-statistic's jitter stacks another ~0.1x on busy runners) — the
plane's absolute cost is bounded by the throughput gate, which stays
at 5%.

Alert hygiene rides along: the on-mode replay is a clean, fault-free
DES, so the declared SLOs must fire ZERO alerts — a false positive here
is an alerting bug, and it fails the bench.
"""
from __future__ import annotations

import os

import numpy as np

from repro.core import UOTConfig
from benchmarks.common import emit
from benchmarks.bench_serve import make_trace, sim_scheduler, _percentiles

REPEATS = 3
SMOKE_REPEATS = 7
OVERHEAD_BAR = 1.05
SMOKE_P99_BAR = 1.5


def _best_replay(trace, cfg, *, lanes, chunk, obs, repeats=REPEATS,
                 slos=None):
    """Best-of-``repeats`` (min makespan, min p99) replays of the trace."""
    best_T, best_p99 = float("inf"), float("inf")
    sched = None
    for _ in range(repeats):
        lat, T, sched = sim_scheduler(trace, cfg, lanes_per_pool=lanes,
                                      chunk_iters=chunk, warmup=False,
                                      obs=obs, slos=slos)
        _, p99 = _percentiles(lat)
        best_T = min(best_T, T)
        best_p99 = min(best_p99, p99)
    return best_T, best_p99, sched


def run():
    smoke = bool(os.environ.get("BENCH_OBS_SMOKE"))
    if smoke:
        n, rate = 16, 200.0
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=30, tol=1e-3)
        shapes = [(24, 100), (40, 120)]
        lanes, chunk = 4, 4
    else:
        n, rate = 80, 200.0
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=200, tol=1e-4)
        shapes = [(200, 300), (224, 320), (256, 384)]
        lanes, chunk = 12, 6
    trace = make_trace(n, rate, seed=3, shapes=shapes,
                       peak_range=(1.0, 8.0), reg=cfg.reg)

    # one shared compile warmup (obs state doesn't brand jit signatures,
    # so one warm pass covers both modes)
    sim_scheduler(trace, cfg, lanes_per_pool=lanes, chunk_iters=chunk,
                  warmup=True, obs=False)

    # the on mode declares real SLO objectives so the operational plane
    # does full per-round work: window tick over every registry metric,
    # burn-rate evaluation for each SLO, flight-recorder round capture
    from repro.obs import default_slos
    slos = default_slos("serve", window=30.0)
    repeats = SMOKE_REPEATS if smoke else REPEATS
    T_off, p99_off, s_off = _best_replay(trace, cfg, lanes=lanes,
                                         chunk=chunk, obs=False,
                                         repeats=repeats)
    T_on, p99_on, s_on = _best_replay(trace, cfg, lanes=lanes,
                                      chunk=chunk, obs=None,
                                      repeats=repeats, slos=slos)

    # the off mode must actually be off, and the on mode actually on
    assert not s_off.obs.tracer.enabled and not s_off.obs.traffic.enabled
    assert s_on.obs.tracer.enabled and s_on.obs.traffic.enabled
    assert len(s_on.obs.tracer.events) > 0
    assert s_on.obs.traffic.totals()["bytes"] > 0
    # ... including the measured-performance instruments: round phases
    # timed (and annotated) when on, null twins when off. The serving
    # round no longer syncs launches to time them, so no kernel cells
    assert s_on.obs.profile.enabled and s_on.obs.profile.cells() == {}
    assert s_on.obs.registry.histogram(
        "profile.phase.serve.chunk").snapshot()["count"] > 0
    assert not s_off.obs.profile.enabled and not s_off.obs.phases.enabled
    # ... and the operational telemetry plane: windows ticked every
    # round, SLOs evaluated, flight rounds recorded when on; null twins
    # when off — so the <= 5% bar covers PR 10's whole plane
    assert s_on.obs.windows.enabled and s_on.obs.windows.samples > 1
    assert s_on.obs.slo.enabled and s_on.obs.slo.states()
    assert s_on.flight.enabled and len(s_on.flight.rounds()) > 0
    assert not s_off.obs.windows.enabled and not s_off.obs.slo.enabled \
        and not s_off.flight.enabled
    # alert hygiene: a clean fault-free DES must fire zero alerts
    clean_alerts = [a for a in s_on.obs.slo.alerts if a.state == "firing"]
    assert not clean_alerts, \
        f"false-positive alerts on a clean replay: {clean_alerts}"
    # the exporter renders the whole bundle as valid Prometheus text
    from repro.obs import parse_prometheus_text
    families = parse_prometheus_text(s_on.exporter.prometheus())
    assert any(k.startswith("serve_") for k in families), sorted(families)[:5]
    # the registry stays live either way: stats() totals must agree
    assert s_off.stats()["completed"] == s_on.stats()["completed"] == n

    tput_ratio = T_on / T_off          # >1 = obs made the replay slower
    p99_ratio = p99_on / p99_off
    p99_bar = SMOKE_P99_BAR if smoke else OVERHEAD_BAR
    tag = "smoke" if smoke else f"n{n}"
    emit(f"obs_off_p99_{tag}", p99_off * 1e6,
         f"throughput={n / T_off:.1f}rps,makespan={T_off:.3f}s")
    emit(f"obs_on_p99_{tag}", p99_on * 1e6,
         f"throughput={n / T_on:.1f}rps,"
         f"events={len(s_on.obs.tracer.events)},"
         f"charges={s_on.obs.traffic.totals()['charges']}")
    emit(f"obs_plane_{tag}", s_on.obs.windows.samples,
         f"slos={len(s_on.obs.slo.states())},alerts=0,"
         f"flight_rounds={len(s_on.flight.rounds())},"
         f"prom_families={len(families)}")
    emit(f"obs_overhead_{tag}", (tput_ratio - 1.0) * 100,
         f"tput_ratio={tput_ratio:.4f},p99_ratio={p99_ratio:.4f},"
         f"bar={OVERHEAD_BAR:.2f}")
    assert tput_ratio <= OVERHEAD_BAR, \
        (f"obs-on makespan {T_on:.4f}s is {tput_ratio:.3f}x obs-off "
         f"{T_off:.4f}s (bar: {OVERHEAD_BAR}x)")
    assert p99_ratio <= p99_bar, \
        (f"obs-on p99 {p99_on * 1e3:.2f}ms is {p99_ratio:.3f}x obs-off "
         f"{p99_off * 1e3:.2f}ms (bar: {p99_bar}x)")
