"""Observability layer: registry semantics, span tracing, HBM-traffic
accounting — and the regressions that ride the same PR (clock/sleep
injection, ``window_dropped`` visibility, lost-result spans).

The traffic tests re-derive every accountant aggregate from its formula
key (``benchmarks.bench_chaos.verify_traffic`` — the same mechanical
check the chaos harness hard-asserts), so a charge that drifts from the
``kernels/ops.py`` dispatch-table formulas fails here first.
"""
import json
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs as obslib
from repro.core import UOTConfig
from repro.kernels import ops
from repro.serve import (QueueFullError, UOTBatchEngine, UOTScheduler,
                         submit_with_retry)
from repro.cluster import ClusterScheduler
from benchmarks.common import make_problem as _common_problem
from benchmarks.bench_chaos import verify_traffic

CFG = UOTConfig(reg=0.1, reg_m=1.0, num_iters=20, tol=1e-3)


def make_problem(m, n, seed, peak=1.0):
    return _common_problem(m, n, reg=CFG.reg, seed=seed, peak=peak)


def bundle(**kw):
    """Isolated obs bundle: no chaining to the process-global one, so
    assertions see exactly this test's charges/events."""
    kw.setdefault("chain", False)
    return obslib.Observability(**kw)


# ---- metrics registry ------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_basics_and_kind_mismatch(self):
        reg = obslib.MetricsRegistry()
        c = reg.counter("x")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert reg.counter("x") is c          # same name -> same metric
        g = reg.gauge("y")
        g.set(2.5)
        assert g.value == 2.5
        with pytest.raises(TypeError):
            reg.gauge("x")                    # kind mismatch
        dump = reg.dump()
        assert dump["counters"]["x"] == 5
        assert dump["gauges"]["y"] == 2.5

    def test_histogram_percentiles_vs_numpy(self):
        """Bucketed estimates land within one 2x bucket factor of the
        exact ``np.percentile`` answer, and inside the observed range."""
        rng = np.random.default_rng(0)
        samples = rng.lognormal(mean=-5.0, sigma=1.5, size=5000)
        h = obslib.MetricsRegistry().histogram("lat")
        for s in samples:
            h.observe(float(s))
        for q in (50, 90, 99):
            est = h.percentile(q)
            exact = float(np.percentile(samples, q))
            assert exact / 2.0 <= est <= exact * 2.0, (q, est, exact)
            assert samples.min() <= est <= samples.max()
        snap = h.snapshot()
        assert snap["count"] == len(samples)
        assert snap["min"] == pytest.approx(float(samples.min()))
        assert snap["max"] == pytest.approx(float(samples.max()))
        assert snap["mean"] == pytest.approx(float(samples.mean()))

    def test_histogram_overflow_clamps_to_observed_max(self):
        h = obslib.MetricsRegistry().histogram(
            "h", buckets=obslib.geometric_buckets(1.0, 8.0))
        for v in (2.0, 1e6):                  # 1e6 overflows the top edge
            h.observe(v)
        assert h.percentile(99) <= 1e6

    def test_parent_chaining_forwards_everything(self):
        parent = bundle()
        child = bundle(parent=parent, chain=True)
        child.registry.counter("n").inc(3)
        child.registry.histogram("h").observe(0.5)
        child.traffic.charge_solve(route="solve", tier="streamed",
                                   M=8, N=16, s=4, T=10)
        assert parent.registry.counter("n").value == 3
        assert parent.registry.histogram("h").snapshot()["count"] == 1
        assert parent.traffic.totals() == child.traffic.totals()

    def test_counter_exact_under_threads(self):
        """Concurrent ``inc`` never drops a count — the property the
        async cluster step loop leans on."""
        c = obslib.MetricsRegistry().counter("hits")

        def hammer():
            for _ in range(10_000):
                c.inc()

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 80_000


# ---- span tracer -----------------------------------------------------------


class TestTracer:
    def test_jsonl_roundtrip_and_audit(self, tmp_path):
        tr = obslib.SpanTracer(clock=lambda: 1.25)
        tr.emit(0, "submit", M=8, N=16, bucket=[64, 128])
        tr.emit(0, "complete", status="ok", iters=12, converged=True)
        tr.emit(1, "submit", M=8, N=16)
        path = tmp_path / "trace.jsonl"
        assert tr.write_jsonl(path) == 3
        reloaded = obslib.SpanTracer.from_events(
            obslib.SpanTracer.load_jsonl(path))
        assert reloaded.events == tr.events
        audit = tr.check_complete(submitted=[0, 1])
        assert audit["total"] == 2 and audit["missing"] == [1]
        assert not audit["multiple"]
        timeline = tr.render_timeline()
        assert isinstance(timeline, str) and timeline

    def test_bounded_events_drop_the_oldest_and_count_them(self):
        reg = obslib.MetricsRegistry()
        tr = obslib.SpanTracer(clock=lambda: 0.0, max_events=16,
                               registry=reg)
        for rid in range(100):
            tr.emit(rid, "submit")
        assert len(tr.events) <= 16
        assert reg.counter("tracer.dropped_events").value == \
            100 - len(tr.events)
        # what is kept is the newest events, in order
        assert [e["rid"] for e in tr.events] == list(
            range(100 - len(tr.events), 100))
        # a bundle's tracer counts its drops in the bundle's registry,
        # and the default bound drops nothing a test produces
        obs = bundle()
        assert obs.tracer.registry is obs.registry
        assert obs.tracer.max_events >= 10 ** 6
        assert obs.registry.get("tracer.dropped_events") is None

    def test_disabled_bundle_swaps_in_null_twins(self):
        obs = bundle(enabled=False)
        obs.tracer.emit(0, "submit")
        assert obs.tracer.events == ()
        assert obs.traffic.charge_solve(route="solve", tier="streamed",
                                        M=8, N=16, s=4, T=10) == 0
        assert obs.traffic.records() == []
        # the registry stays live either way: stats() totals depend on it
        obs.registry.counter("still.live").inc()
        assert obs.registry.counter("still.live").value == 1


# ---- dispatch observer (kernels/ops.py) ------------------------------------


class TestDispatchObserver:
    def test_auto_routing_reports_decisions(self):
        K, a, b = make_problem(24, 32, 0)
        seen = []

        def cb(kind, **kw):
            seen.append((kind, kw))

        with ops.dispatch_observer(cb):
            ops.solve_fused(jnp.asarray(K), jnp.asarray(a), jnp.asarray(b),
                            CFG, impl="auto")
        assert seen, "auto dispatch must report its routing decision"
        for kind, kw in seen:
            assert kind in ("resident", "streamed")
            assert kw["M"] >= 24 and kw["N"] >= 32
            assert kw["itemsize"] in (2, 4)
            assert kw["num_iters"] == CFG.num_iters

    def test_explicit_impl_makes_no_routing_call(self):
        K, a, b = make_problem(24, 32, 0)
        seen = []
        with ops.dispatch_observer(lambda kind, **kw: seen.append(kind)):
            ops.solve_fused(jnp.asarray(K), jnp.asarray(a), jnp.asarray(b),
                            CFG, impl=None)
        assert seen == []


# ---- scheduler-driven spans + traffic --------------------------------------


def run_scheduler(n_dense=4, n_points=2, **kw):
    kw.setdefault("obs", bundle())
    kw.setdefault("impl", "jnp")
    sched = UOTScheduler(CFG, lanes_per_pool=4, chunk_iters=4, **kw)
    rids = []
    for i in range(n_dense):
        rids.append(sched.submit(*make_problem(24, 100, i)))
    rng = np.random.default_rng(7)
    for i in range(n_points):
        x = rng.normal(size=(16, 2)).astype(np.float32)
        y = rng.normal(size=(90, 2)).astype(np.float32)
        a = np.full(16, 1.0 / 16, np.float32)
        b = np.full(90, 1.0 / 90, np.float32)
        rids.append(sched.submit_points(x, y, a, b))
    sched.run()
    return sched, rids


class TestSchedulerObservability:
    def test_zero_span_loss_and_lifecycle_events(self):
        sched, rids = run_scheduler()
        audit = sched.obs.tracer.check_complete(submitted=rids)
        assert audit["total"] == len(rids)
        assert not audit["missing"] and not audit["multiple"]
        kinds = {e["event"] for e in sched.obs.tracer.events}
        assert {"submit", "place", "chunk", "evict", "complete"} <= kinds
        assert sched.stats()["completed"] == len(rids)

    def test_traffic_matches_dispatch_table_fp32(self):
        sched, _ = run_scheduler()
        recs = sched.obs.traffic.records()
        verify_traffic(recs)                  # formula-by-formula
        admits = [r for r in recs if r["kind"] == "admit"]
        assert {r["source"] for r in admits} == {"dense", "implicit"}
        imp = next(r for r in admits if r["source"] == "implicit")
        assert imp["d"] == 2 and imp["itemsize"] == 4
        chunks = [r for r in recs if r["kind"] == "chunk"]
        assert chunks and all(r["route"] == "lane" and r["itemsize"] == 4
                              for r in chunks)

    def test_traffic_bf16_storage_halves_itemsize(self):
        sched, _ = run_scheduler(storage_dtype=jnp.bfloat16)
        recs = sched.obs.traffic.records()
        verify_traffic(recs)
        chunks = [r for r in recs if r["kind"] == "chunk"]
        assert chunks and all(r["itemsize"] == 2 for r in chunks)

    def test_auto_impl_resident_chunks_charge_resident_tier(self):
        sched, _ = run_scheduler(impl="auto")
        recs = sched.obs.traffic.records()
        verify_traffic(recs)
        resident_routed = sched.obs.registry.counter(
            "serve.dispatch.resident").value
        chunk_tiers = {r["tier"] for r in recs if r["kind"] == "chunk"}
        if resident_routed:
            assert "resident" in chunk_tiers
        else:
            assert chunk_tiers == {"streamed"}

    def test_obs_false_still_counts_but_traces_nothing(self):
        sched, rids = run_scheduler(obs=False)
        assert not sched.obs.tracer.enabled
        assert sched.obs.tracer.events == ()
        assert sched.obs.traffic.records() == []
        assert sched.stats()["completed"] == len(rids)

    def test_chains_to_global_by_default(self):
        obslib.reset_global()
        try:
            sched, rids = run_scheduler(obs=None)
            g = obslib.get_global()
            assert (g.registry.counter("serve.submitted").value
                    == len(rids))
            assert g.traffic.totals()["bytes"] > 0
            # tracers are NOT globally merged (rid spaces per-scheduler)
            assert sched.obs.tracer.events
        finally:
            obslib.reset_global()

    def test_window_dropped_exposed_via_stats(self):
        """Regression: trimming the telemetry window must be visible —
        silent narrowing made aggregate stats lie about coverage."""
        sched, rids = run_scheduler(n_dense=6, n_points=0, max_log=2)
        st = sched.stats()
        dropped = st["window_dropped"]
        assert dropped["requests"] > 0
        assert (dropped["requests"]
                == sched.obs.registry.counter(
                    "serve.window_dropped_requests").value)
        assert len(sched.request_log) <= 2

    def test_lost_results_emit_lost_spans(self):
        sched, rids = run_scheduler(n_dense=4, n_points=0, max_results=1)
        assert sched.stats()["lost_results"] > 0
        lost = [e for e in sched.obs.tracer.events if e["event"] == "lost"]
        assert len(lost) == sched.stats()["lost_results"]
        # losing a coupling does not un-complete the request
        audit = sched.obs.tracer.check_complete(submitted=rids)
        assert not audit["missing"] and not audit["multiple"]


# ---- clock / sleep injection ----------------------------------------------


class TestSleepInjection:
    def _assert_injected_sleep_used(self, sched, submit, monkeypatch):
        def boom(_):
            raise AssertionError("time.sleep called despite injected sleep")

        monkeypatch.setattr(time, "sleep", boom)
        slept = []
        sched.sleep = slept.append
        submit()                              # fills max_queue=1
        with pytest.raises(QueueFullError):
            submit_with_retry(sched, *make_problem(24, 100, 9), attempts=3,
                              base_delay=1e-4)
        assert len(slept) == 2                # attempts-1 backoff sleeps
        assert all(d > 0 for d in slept)

    def test_scheduler_resolves_injected_sleep(self, monkeypatch):
        sched = UOTScheduler(CFG, lanes_per_pool=2, impl="jnp",
                             max_queue=1, obs=bundle())
        self._assert_injected_sleep_used(
            sched, lambda: sched.submit(*make_problem(24, 100, 0)),
            monkeypatch)

    def test_cluster_scheduler_resolves_injected_sleep(self, monkeypatch):
        cs = ClusterScheduler(CFG, num_devices=1, lanes_per_device=2,
                              impl="jnp", max_queue=1, obs=bundle())
        self._assert_injected_sleep_used(
            cs, lambda: cs.submit(*make_problem(24, 100, 0)), monkeypatch)


# ---- cluster scheduler: async thread safety + gang traffic -----------------


class TestClusterObservability:
    def test_async_step_loop_keeps_counters_exact(self):
        """Metric writes from the async chunk loop interleave with host
        threads hammering the same registry; totals stay exact."""
        obs = bundle()
        cs = ClusterScheduler(CFG, num_devices=2, lanes_per_device=2,
                              impl="jnp", step_mode="async", obs=obs)
        rids = [cs.submit(*make_problem(24, 100, i)) for i in range(6)]
        c = obs.registry.counter("test.hammer")

        def hammer():
            for _ in range(10_000):
                c.inc()

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        cs.run()
        for t in threads:
            t.join()
        assert c.value == 40_000
        assert cs.stats()["completed"] == len(rids)
        assert obs.registry.counter("cluster.completed").value == len(rids)
        audit = cs.obs.tracer.check_complete(submitted=rids)
        assert not audit["missing"] and not audit["multiple"]
        verify_traffic(cs.obs.traffic.records())

    def test_gang_route_charges_collective_bytes(self):
        cs = ClusterScheduler(CFG, num_devices=2, lanes_per_device=2,
                              impl="jnp", gang="auto",
                              lane_budget=lambda M, N: False, obs=bundle())
        rid = cs.submit(*make_problem(24, 100, 0))
        cs.run()
        recs = cs.obs.traffic.records()
        verify_traffic(recs)
        gang = [r for r in recs if r["route"] == "gang"]
        assert len(gang) == 1 and gang[0]["kind"] == "solve"
        assert gang[0]["coll_bytes"] > 0
        assert any(e["event"] == "gang" for e in cs.obs.tracer.events
                   if e["rid"] == rid)
        audit = cs.obs.tracer.check_complete(submitted=[rid])
        assert not audit["missing"] and not audit["multiple"]


# ---- batch engine (tier 2) -------------------------------------------------


class TestEngineObservability:
    def test_flush_charges_route_flush_per_request(self):
        obs = bundle()
        eng = UOTBatchEngine(CFG, max_batch=8, impl="jnp", obs=obs)
        for i in range(3):
            eng.submit(*make_problem(24, 100, i))
        rng = np.random.default_rng(3)
        eng.submit_points(rng.normal(size=(16, 2)).astype(np.float32),
                          rng.normal(size=(90, 2)).astype(np.float32),
                          np.full(16, 1.0 / 16, np.float32),
                          np.full(90, 1.0 / 90, np.float32))
        eng.flush()
        reg = obs.registry
        assert reg.counter("engine.submitted").value == 4
        assert reg.counter("engine.flushes").value == 1
        assert reg.counter("engine.flushed").value == 4
        recs = obs.traffic.records()
        verify_traffic(recs)
        solves = [r for r in recs if r["kind"] == "solve"]
        assert solves and all(r["route"] == "flush" for r in solves)
        assert sum(r["count"] for r in solves) == 4
        assert {r["source"] for r in solves} == {"dense", "implicit"}


# ---- direct formula spot checks -------------------------------------------


class TestFormulas:
    M, N, d = 64, 128, 3

    def test_cost_source(self):
        assert obslib.cost_source_bytes(self.M, self.N, 4) == 64 * 128 * 4
        assert obslib.cost_source_bytes(self.M, self.N, 2) == 64 * 128 * 2
        assert (obslib.cost_source_bytes(self.M, self.N, 4,
                                         source="implicit", d=self.d)
                == (64 + 128) * 4 * 4)

    @pytest.mark.parametrize("s", [4, 2])
    def test_solve_tiers(self, s):
        G = 64 * 128 * s
        assert (obslib.solve_bytes(self.M, self.N, s, 10)
                == G + 2 * 64 * 128 * s * 10)
        assert (obslib.solve_bytes(self.M, self.N, s, 10, tier="resident")
                == G + 2 * 64 * 128 * s)
        Gi = (64 + 128) * 4 * 4
        assert (obslib.solve_bytes(self.M, self.N, s, 10, tier="resident",
                                   source="implicit", d=self.d)
                == Gi + 64 * 128 * s)

    @pytest.mark.parametrize("s", [4, 2])
    def test_chunk_tiers(self, s):
        assert (obslib.chunk_bytes(8, self.M, self.N, s, 5)
                == 2 * 8 * 64 * 128 * s * 5)
        assert (obslib.chunk_bytes(8, self.M, self.N, s, 5,
                                   tier="resident")
                == 2 * 8 * 64 * 128 * s)

    def test_gang_and_flops(self):
        assert obslib.gang_collective_bytes(128, 10) == 2 * 128 * 4 * 10
        assert (obslib.modeled_flops(self.M, self.N, 10, lanes=3)
                == 4 * 64 * 128 * 10 * 3)
