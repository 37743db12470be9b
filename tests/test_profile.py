"""Measured-performance layer: phase timers, kernel-launch profiling,
the persistent measurement store, measurement-driven dispatch, and the
perf-regression gate.

The dispatch tests exercise the real ``ops`` auto-resolution — a store
claiming streamed is faster must actually flip a resident-eligible
solve to the streamed tier, and an empty store must leave the static
``resident_fits`` verdict untouched.
"""
import collections
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs as obslib
from repro.core import UOTConfig
from repro.core.predict import measured_seconds_per_iter
from repro.kernels import ops
from repro.obs.profile import cell_key, parse_cell_key
from repro.obs.measure import (MeasurementMismatch, MeasurementStore,
                               MeasuredDispatch, machine_fingerprint)
from repro.serve import UOTScheduler
from repro.cluster import ClusterScheduler
from benchmarks.common import bench_meta, check_payload

CFG = UOTConfig(reg=0.1, reg_m=1.0, num_iters=20, tol=1e-3)


def bundle(**kw):
    kw.setdefault("chain", False)
    return obslib.Observability(**kw)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _problem(m, n, seed=0):
    rng = np.random.default_rng(seed)
    K = rng.uniform(0.1, 1.0, size=(m, n)).astype(np.float32)
    a = rng.uniform(0.5, 1.5, size=m).astype(np.float32)
    b = rng.uniform(0.5, 1.5, size=n).astype(np.float32)
    return K, a / a.sum(), b / b.sum() * 1.2


# ---- cell keys -------------------------------------------------------------


class TestCellKey:
    def test_round_trip(self):
        key = cell_key("chunk", 64, 128, 4, "streamed", "implicit",
                       lanes=8, iters=6)
        assert key == "chunk|64x128|s4|streamed|implicit|L8|T6"
        p = parse_cell_key(key)
        assert p == {"kernel": "chunk", "M": 64, "N": 128, "itemsize": 4,
                     "impl": "streamed", "source": "implicit", "lanes": 8,
                     "iters": 6}


# ---- phase timer -----------------------------------------------------------


class TestPhaseTimer:
    def test_nested_total_and_exclusive(self):
        reg = obslib.MetricsRegistry()
        clk = FakeClock()
        ph = obslib.PhaseTimer(reg, clock=clk)
        with ph.phase("outer"):
            clk.t = 1.0
            with ph.phase("inner"):
                clk.t = 3.0
            clk.t = 4.0
        outer = reg.histogram("profile.phase.outer").snapshot()
        outer_self = reg.histogram("profile.phase.outer.self").snapshot()
        inner = reg.histogram("profile.phase.inner").snapshot()
        inner_self = reg.histogram("profile.phase.inner.self").snapshot()
        assert outer["sum"] == pytest.approx(4.0)   # 0 -> 4
        assert inner["sum"] == pytest.approx(2.0)   # 1 -> 3
        # outer exclusive = total minus the nested child
        assert outer_self["sum"] == pytest.approx(2.0)
        assert inner_self["sum"] == pytest.approx(2.0)
        assert outer["count"] == inner["count"] == 1

    def test_exception_still_records(self):
        reg = obslib.MetricsRegistry()
        clk = FakeClock()
        ph = obslib.PhaseTimer(reg, clock=clk)
        with pytest.raises(ValueError):
            with ph.phase("boom"):
                clk.t = 2.0
                raise ValueError("x")
        assert reg.histogram("profile.phase.boom").snapshot()["sum"] == \
            pytest.approx(2.0)

    def test_null_twin(self):
        ph = obslib.NullPhaseTimer()
        assert not ph.enabled
        with ph.phase("anything"):
            pass


# ---- kernel profiler -------------------------------------------------------


class TestKernelProfiler:
    KW = dict(kernel="solve", M=64, N=128, itemsize=4, impl="resident")

    def test_first_call_split_from_steady_state(self):
        reg = obslib.MetricsRegistry()
        prof = obslib.KernelProfiler(reg)
        key = cell_key("solve", 64, 128, 4, "resident")
        prof.observe_launch(seconds=0.5, **self.KW)     # compile call
        prof.observe_launch(seconds=0.010, **self.KW)
        prof.observe_launch(seconds=0.020, **self.KW)
        prof.observe_launch(seconds=0.030, **self.KW)
        # the 500ms compile call must not pollute the steady median
        assert prof.median_us(key) == pytest.approx(20_000.0)
        cells = prof.cells()
        assert cells[key]["count"] == 4
        assert cells[key]["first_us"] == pytest.approx(500_000.0)
        assert reg.histogram("profile.compile." + key).snapshot()[
            "count"] == 1
        assert reg.histogram("profile.kernel." + key).snapshot()[
            "count"] == 3

    def test_median_none_until_steady_sample(self):
        prof = obslib.KernelProfiler()
        key = cell_key("solve", 64, 128, 4, "resident")
        assert prof.median_us(key) is None
        prof.observe_launch(seconds=0.5, **self.KW)
        assert prof.median_us(key) is None              # compile only
        prof.observe_launch(seconds=0.010, **self.KW)
        assert prof.median_us(key) == pytest.approx(10_000.0)

    def test_null_twin(self):
        prof = obslib.NullKernelProfiler()
        prof.observe_launch(kernel="solve", M=1, N=1, itemsize=4,
                            impl="resident", seconds=1.0)
        assert prof.cells() == {}
        assert not prof.enabled


# ---- measurement store -----------------------------------------------------


class TestMeasurementStore:
    def test_ingest_and_round_trip(self, tmp_path):
        prof = obslib.KernelProfiler()
        kw = dict(kernel="chunk", M=64, N=128, itemsize=4, impl="streamed",
                  lanes=4, iters=6)
        prof.observe_launch(seconds=0.5, **kw)
        prof.observe_launch(seconds=0.010, **kw)
        store = MeasurementStore()
        assert store.ingest(prof) == 1
        # idempotent: profiler cells are cumulative, re-ingest replaces
        assert store.ingest(prof) == 1
        path = tmp_path / "measure.json"
        store.save(path)
        loaded = MeasurementStore.load(path)
        key = cell_key("chunk", 64, 128, 4, "streamed", lanes=4, iters=6)
        assert loaded.us_per_call(key) == pytest.approx(10_000.0)
        assert loaded.fingerprint["id"] == machine_fingerprint()["id"]

    def test_foreign_fingerprint_rejected(self, tmp_path):
        fp = dict(machine_fingerprint())
        fp["id"] = "feedfeedfeed"
        store = MeasurementStore(fingerprint=fp)
        store.record(cell_key("solve", 8, 8, 4, "resident"), 100.0, count=3)
        path = tmp_path / "foreign.json"
        store.save(path)
        with pytest.raises(MeasurementMismatch):
            MeasurementStore.load(path)
        loaded = MeasurementStore.load(path, allow_mismatch=True)
        assert loaded.cells

    def test_schema_mismatch_rejected(self, tmp_path):
        path = tmp_path / "old.json"
        path.write_text(json.dumps({"schema_version": 99, "cells": {}}))
        with pytest.raises(MeasurementMismatch):
            MeasurementStore.load(path)

    def test_us_per_lane_iter_normalizes_and_weights(self):
        store = MeasurementStore(fingerprint={"id": "t"})
        # 2 steady samples at 10 us/lane-iter, 1 steady at 20
        store.record(cell_key("chunk", 64, 128, 4, "streamed",
                              lanes=2, iters=5), 100.0, count=3)
        store.record(cell_key("chunk", 64, 128, 4, "streamed",
                              lanes=4, iters=5), 400.0, count=2)
        out = store.us_per_lane_iter(kernel="chunk", M=64, N=128)
        assert out == pytest.approx((2 * 10.0 + 1 * 20.0) / 3)
        # compile-only cells (count=1 -> 0 steady samples) don't count
        store2 = MeasurementStore(fingerprint={"id": "t"})
        store2.record(cell_key("chunk", 64, 128, 4, "streamed"),
                      100.0, count=1)
        assert store2.us_per_lane_iter(kernel="chunk") is None

    def test_achieved_bandwidth(self):
        store = MeasurementStore(fingerprint={"id": "t"})
        key = cell_key("chunk", 64, 128, 4, "streamed", lanes=2, iters=5)
        store.record(key, 100.0, count=3)
        ach = store.achieved()
        nbytes = obslib.chunk_bytes(2, 64, 128, 4, 5, tier="streamed")
        assert ach[key]["modeled_bytes"] == nbytes
        assert ach[key]["achieved_gbps"] == \
            pytest.approx(nbytes / 100e-6 / 1e9)
        # no device kind in the fingerprint: no peak, no roofline share
        assert ach[key]["measured_roofline_fraction"] is None
        v5e = MeasurementStore(fingerprint={"id": "t",
                                            "device_kind": "TPU v5 lite"})
        v5e.record(key, 100.0, count=3)
        assert v5e.achieved()[key]["measured_roofline_fraction"] == \
            pytest.approx(nbytes / 100e-6 / 819e9)
        cpu = MeasurementStore(fingerprint={"id": "t", "device_kind": "cpu"})
        cpu.record(key, 100.0, count=3)
        assert cpu.achieved()[key]["measured_roofline_fraction"] is None


# ---- measurement-driven dispatch -------------------------------------------


def _solve_store(M, N, *, res_us, str_us, itemsize=4, iters=CFG.num_iters):
    store = MeasurementStore(fingerprint={"id": "t"})
    store.record(cell_key("solve", M, N, itemsize, "resident", iters=iters),
                 res_us, count=3)
    store.record(cell_key("solve", M, N, itemsize, "streamed", iters=iters),
                 str_us, count=3)
    return store


class TestMeasuredDispatch:
    def test_advises_faster_tier_or_defers(self):
        adv = MeasuredDispatch(_solve_store(32, 32, res_us=200.0,
                                            str_us=100.0))
        assert adv.advise(M=32, N=32, itemsize=4) == "streamed"
        adv = MeasuredDispatch(_solve_store(32, 32, res_us=100.0,
                                            str_us=200.0))
        assert adv.advise(M=32, N=32, itemsize=4) == "resident"
        # one-sided data -> no opinion
        one = MeasurementStore(fingerprint={"id": "t"})
        one.record(cell_key("solve", 32, 32, 4, "resident"), 100.0, count=3)
        assert MeasuredDispatch(one).advise(M=32, N=32, itemsize=4) is None
        assert MeasuredDispatch(MeasurementStore(
            fingerprint={"id": "t"})).advise(M=32, N=32, itemsize=4) is None

    def test_margin_biases_toward_static(self):
        store = _solve_store(32, 32, res_us=100.0, str_us=80.0)
        assert MeasuredDispatch(store).advise(
            M=32, N=32, itemsize=4) == "streamed"
        # 1.25x faster doesn't clear a 2x margin
        assert MeasuredDispatch(store, margin=2.0).advise(
            M=32, N=32, itemsize=4) == "resident"

    def test_ops_auto_routes_by_measurement(self):
        """The acceptance flip: same call, same shape — the store's
        verdict decides the tier."""
        M = N = 32
        assert ops.resident_fits(M, N, CFG)
        K, a, b = _problem(M, N)
        Ks = jnp.asarray(K)[None], jnp.asarray(a)[None], jnp.asarray(b)[None]

        def solve():
            with ops.dispatch_counters() as counters:
                ops.solve_fused_batched(Ks[0], Ks[1], Ks[2], CFG,
                                        impl="auto", interpret=True)
            return counters

        # no advisor: the static budget says resident
        c = solve()
        assert c == {"resident": 1, "streamed": 0}
        # store says streamed is faster: the same call flips tiers
        slow_res = MeasuredDispatch(
            _solve_store(M, N, res_us=900.0, str_us=100.0))
        with ops.dispatch_advisor(slow_res):
            c = solve()
        assert c == {"resident": 0, "streamed": 1}
        # store agreeing with the static budget keeps resident
        fast_res = MeasuredDispatch(
            _solve_store(M, N, res_us=100.0, str_us=900.0))
        with ops.dispatch_advisor(fast_res):
            c = solve()
        assert c == {"resident": 1, "streamed": 0}
        # an empty store has no opinion: static budget again
        empty = MeasuredDispatch(MeasurementStore(fingerprint={"id": "t"}))
        with ops.dispatch_advisor(empty):
            c = solve()
        assert c == {"resident": 1, "streamed": 0}

    def test_advice_cannot_override_static_semantics(self):
        """A shape over the VMEM budget is streamed no matter what the
        measurements claim — correctness constraints are not advisory."""
        M, N = 2048, 4096
        assert not ops.resident_fits(M, N, CFG)
        lie = MeasuredDispatch(_solve_store(M, N, res_us=1.0, str_us=900.0))
        K, a, b = _problem(M, N)
        with ops.dispatch_advisor(lie), ops.dispatch_counters() as c:
            ops.solve_fused_batched(jnp.asarray(K)[None],
                                    jnp.asarray(a)[None],
                                    jnp.asarray(b)[None], CFG,
                                    impl="auto", interpret=True)
        assert c == {"resident": 0, "streamed": 1}


# ---- measured seconds-per-iter ---------------------------------------------


class TestMeasuredSecondsPerIter:
    def _chunk_store(self, us=120.0, lanes=4, iters=6, M=64, N=128):
        store = MeasurementStore(fingerprint={"id": "t"})
        store.record(cell_key("chunk", M, N, 4, "streamed",
                              lanes=lanes, iters=iters), us, count=3)
        return store

    def test_converts_store_rate(self):
        store = self._chunk_store(us=120.0, lanes=4, iters=6)
        assert measured_seconds_per_iter(store) == \
            pytest.approx(120e-6 / 24)
        assert measured_seconds_per_iter(None) is None
        assert measured_seconds_per_iter(
            MeasurementStore(fingerprint={"id": "t"})) is None

    def test_serve_scheduler_uses_store_before_any_completion(self):
        store = self._chunk_store(us=240.0, lanes=4, iters=6)
        sched = UOTScheduler(CFG, lanes_per_pool=2, chunk_iters=5,
                             interpret=True, measurements=store)
        assert sched._seconds_per_iter() == pytest.approx(240e-6 / 24)
        # per-bucket lookup falls back to the aggregate for a cold bucket
        assert sched._seconds_per_iter((999, 999)) == \
            pytest.approx(240e-6 / 24)
        # pinned wins over measured: a pinned value asserts units
        pinned = UOTScheduler(CFG, lanes_per_pool=2, chunk_iters=5,
                              interpret=True, measurements=store,
                              seconds_per_iter=1.5)
        assert pinned._seconds_per_iter() == 1.5

    def test_cluster_scheduler_uses_store(self):
        store = self._chunk_store(us=240.0, lanes=4, iters=6)
        sched = ClusterScheduler(CFG, num_devices=1, lanes_per_device=2,
                                 chunk_iters=5, interpret=True,
                                 measurements=store)
        assert sched._seconds_per_iter() == pytest.approx(240e-6 / 24)


# ---- scheduler integration -------------------------------------------------


class TestSchedulerProfiling:
    # no tol: every request runs the full 20 iterations = 4 chunks, so
    # the chunk cell gets steady-state samples past its compile call
    CFG_RUN = UOTConfig(reg=0.1, reg_m=1.0, num_iters=20)

    def _drive(self, sched, n=2):
        rids = []
        for i in range(n):
            K, a, b = _problem(12, 16, seed=i)
            rids.append(sched.submit(K, a, b))
        for _ in range(12):
            sched.step()
        for rid in rids:
            sched.poll(rid)
        return sched

    def test_serve_phases_and_cells(self):
        # the serving round no longer syncs each chunk launch to time it
        # (a profiler trace gives chunk device time): phases record,
        # kernel cells must not
        sched = self._drive(UOTScheduler(
            self.CFG_RUN, lanes_per_pool=2, chunk_iters=5, interpret=True,
            obs=bundle()))
        assert sched.obs.profile.enabled
        assert sched.obs.profile.cells() == {}
        reg = sched.obs.registry.dump()["histograms"]
        assert not any(k.startswith(("profile.kernel.", "profile.compile."))
                       for k in reg)
        for name in ("serve.evict", "serve.evict.read", "serve.admit",
                     "serve.admit.launch", "serve.chunk", "serve.upkeep",
                     "serve.poll"):
            full = f"profile.phase.{name}"
            assert reg[full]["count"] > 0, full
            assert f"{full}.self" in reg

    def test_cluster_phases_and_cells(self):
        sched = self._drive(ClusterScheduler(
            self.CFG_RUN, num_devices=1, lanes_per_device=2, chunk_iters=5,
            interpret=True, obs=bundle()))
        assert sched.obs.profile.cells()
        reg = sched.obs.registry.dump()["histograms"]
        for name in ("cluster.prep", "cluster.evict", "cluster.admit",
                     "cluster.gang", "cluster.chunk", "cluster.poll"):
            assert reg[f"profile.phase.{name}"]["count"] > 0, name

    def test_async_cluster_skips_launch_profiling(self):
        # the per-launch sync would destroy the async mode's host/device
        # overlap — phases still record, kernel cells must not
        sched = self._drive(ClusterScheduler(
            self.CFG_RUN, num_devices=1, lanes_per_device=2, chunk_iters=5,
            interpret=True, step_mode="async", obs=bundle()))
        assert sched.obs.profile.cells() == {}
        reg = sched.obs.registry.dump()["histograms"]
        assert reg["profile.phase.cluster.chunk"]["count"] > 0

    def test_cells_roll_up_to_global(self):
        # default (chained) bundles feed the process-global profiler's
        # cells, so OBS_<suite>.json dumps carry measured cells; the
        # cluster scheduler's sync mode is what still times launches
        obslib.reset_global()
        sched = self._drive(ClusterScheduler(
            self.CFG_RUN, num_devices=1, lanes_per_device=2, chunk_iters=5,
            interpret=True, step_mode="sync"))
        try:
            local = sched.obs.profile.cells()
            global_cells = obslib.get_global().profile.cells()
            assert local and set(local) <= set(global_cells)
            # ingest -> the store now predicts this scheduler's chunk cost
            store = MeasurementStore()
            assert store.ingest(obslib.get_global().profile) > 0
            assert measured_seconds_per_iter(store) > 0
        finally:
            obslib.reset_global()

    def test_round_phases_nest_in_a_profiler_trace(self, tmp_path):
        # the phases are profiler annotations: a trace taken around the
        # rounds holds every phase, each inside the span it belongs to
        import jax
        from bench import trace as btrace
        sched = UOTScheduler(self.CFG_RUN, lanes_per_pool=2, chunk_iters=5,
                             interpret=True, obs=bundle())
        rng = np.random.default_rng(3)
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=btrace.options())
        try:
            for i in range(2):
                with jax.profiler.TraceAnnotation("serve.submit"):
                    sched.submit(*_problem(12, 16, seed=i))
                _, a, b = _problem(10, 14, seed=10 + i)
                with jax.profiler.TraceAnnotation("serve.submit"):
                    sched.submit_points(
                        rng.uniform(size=(10, 3)).astype(np.float32),
                        rng.uniform(size=(14, 3)).astype(np.float32), a, b,
                        scale=3.0)
            for _ in range(12):
                with jax.profiler.TraceAnnotation("serve.step"):
                    sched.step()
        finally:
            jax.profiler.stop_trace()
        assert sched.stats()["completed"] == 4
        spans = collections.defaultdict(list)
        for name, start, dur in btrace.load(
                btrace.find_xplane(str(tmp_path)))["host"]:
            spans[name].append((start, start + dur))
        parent_of = {"serve.evict": "serve.step",
                     "serve.evict.read": "serve.evict",
                     "serve.admit": "serve.step",
                     "serve.admit.launch": "serve.admit",
                     "serve.chunk": "serve.step",
                     "serve.upkeep": "serve.step",
                     "serve.points": "serve.submit"}
        for child, parent in parent_of.items():
            assert spans[child], child
            for s, e in spans[child]:
                assert any(ps <= s and e <= pe for ps, pe in spans[parent]), \
                    (child, parent)
        assert len(spans["serve.evict.read"]) == 4
        assert len(spans["serve.points"]) == 2
        assert len(spans["serve.upkeep"]) == 12

    def test_obs_false_profiles_nothing(self):
        sched = self._drive(UOTScheduler(
            self.CFG_RUN, lanes_per_pool=2, chunk_iters=5, interpret=True,
            obs=False))
        assert not sched.obs.profile.enabled
        assert sched.obs.profile.cells() == {}
        assert not any(k.startswith("profile.")
                       for k in sched.obs.registry.dump()["histograms"])


# ---- perf-regression gate --------------------------------------------------


def _payload(us_by_name, fp_id="same", meta=True):
    p = {"records": [{"name": n, "us_per_call": us, "derived": ""}
                     for n, us in us_by_name.items()]}
    if meta:
        p["meta"] = {"schema_version": 2, "fingerprint": {"id": fp_id}}
    return p


class TestCheckPayload:
    def test_identical_passes(self):
        base = _payload({"a": 1000.0, "b": 2000.0})
        out = check_payload(_payload({"a": 1000.0, "b": 2000.0}), base)
        assert out["status"] == "ok" and out["compared"] == 2

    def test_injected_slowdown_fails(self):
        base = _payload({"a": 1000.0, "b": 2000.0})
        out = check_payload(_payload({"a": 2000.0, "b": 2000.0}), base,
                            threshold=1.3)
        assert out["status"] == "fail"
        assert [f["name"] for f in out["failures"]] == ["a"]
        assert out["failures"][0]["ratio"] == pytest.approx(2.0)

    def test_within_threshold_passes(self):
        base = _payload({"a": 1000.0})
        assert check_payload(_payload({"a": 1250.0}), base,
                             threshold=1.3)["status"] == "ok"

    def test_machine_mismatch_skips(self):
        base = _payload({"a": 1000.0}, fp_id="other")
        out = check_payload(_payload({"a": 9000.0}), base)
        assert out["status"] == "skip"
        assert "fingerprint" in out["reason"]

    def test_missing_meta_skips(self):
        base = _payload({"a": 1000.0}, meta=False)
        assert check_payload(_payload({"a": 9000.0}),
                             base)["status"] == "skip"

    def test_noise_floor_and_sentinels_ignored(self):
        # sub-min_us baselines and non-positive sentinels never fail
        base = _payload({"tiny": 10.0, "neg": -1.0, "big": 1000.0})
        fresh = _payload({"tiny": 90.0, "neg": -1.0, "big": 1100.0})
        out = check_payload(fresh, base, min_us=50.0)
        assert out["status"] == "ok" and out["compared"] == 1


class TestBenchMeta:
    def test_provenance_keys(self):
        meta = bench_meta()
        assert meta["schema_version"] == 2
        assert meta["fingerprint"]["id"] == machine_fingerprint()["id"]
        for k in ("git_sha", "jax", "jaxlib", "backend", "device_kind"):
            assert k in meta
