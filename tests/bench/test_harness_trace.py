"""The trace reduction, on a trace recorded on the chip and on small
made-up traces, and the readers of the per-layer metrics."""
import json
import types

import pytest

from conftest import REPO
from bench import bytecount, layers, trace

RECORDED = json.loads(
    (REPO / "tests" / "bench" / "data" / "trace_v5e_solve.json").read_text())


def test_recorded_chip_trace_reduces_to_busy_idle_and_ops():
    r = trace.reduce(RECORDED["events"])
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.297158358, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.252433966, rel=1e-9)
    ops = dict(r["ops"])
    # the loop's copy of the coupling and the fused iteration kernel
    assert list(ops)[:2] == ["copy", "batched_fused_iteration_frow"]
    assert "while" not in ops
    assert sum(ops.values()) <= r["busy_s"] * 1.0000001
    idle = dict(r["idle_gaps"])
    assert max(idle, key=idle.get) == "bench.idle"
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["busy_in"]["bench.solve"] <= r["busy_s"]


def _events(devices, host):
    return {"devices": devices, "host": host}


def test_busy_is_the_union_of_ops_inside_the_window():
    ev = _events({"/device:TPU:0": [["%fusion.1 = f", 0, 10],
                                    ["%fusion.2 = f", 5, 10],
                                    ["%copy.3 = c", 30, 10],
                                    ["%copy.4 = c", 95, 20]]},
                 [["bench.window", 0, 100], ["bench.solve", 0, 50],
                  ["serve.step", 50, 50]])
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)        # 0-15, 30-40, 95-100
    assert dict(r["ops"]) == pytest.approx({"fusion": 20e-9, "copy": 15e-9})
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.solve": 25e-9, "serve.step": 45e-9})   # 15-50 and 50-95
    assert r["busy_in"]["bench.solve"] == pytest.approx(25e-9)


def test_idle_gap_takes_the_innermost_open_span_or_host_idle():
    ev = _events({"/device:TPU:0": [["%a = x", 0, 10], ["%b = x", 90, 10]]},
                 [["bench.window", 0, 100], ["serve.step", 10, 40],
                  ["bench.idle", 20, 10]])
    r = trace.reduce(ev)
    # the gap 10-90: bench.idle 20-30 inside serve.step 10-50, then nothing
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"serve.step": 30e-9, "bench.idle": 10e-9, "host_idle": 40e-9})


def test_busy_is_averaged_over_the_devices():
    ev = _events({
        "/device:TPU:0": [["%fusion.1 = f", 0, 40],
                          ["%all-reduce.2 = f32[8] all-reduce(x)", 30, 30]],
        "/device:TPU:1": [["%fusion.1 = f", 0, 60]]},
        [["bench.window", 0, 100]])
    r = trace.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(60e-9)
    assert dict(r["ops"]) == pytest.approx({"fusion": 50e-9,
                                            "all-reduce": 15e-9})


def test_containers_count_as_busy_not_as_ops():
    ev = _events({"/device:TPU:0": [["%while.1 = w", 0, 100],
                                    ["%fusion.2 = f", 10, 20]]},
                 [["bench.window", 0, 100]])
    r = trace.reduce(ev)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert dict(r["ops"]) == pytest.approx({"fusion": 20e-9})


def test_nothing_to_read_gives_none():
    assert trace.reduce(_events({}, [["bench.window", 0, 10]])) is None
    assert trace.reduce(_events({"/device:TPU:0": [["%a = x", 0, 1]]},
                                [])) is None


def test_load_reads_host_spans_of_a_real_trace_file(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.solve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(trace.find_xplane(str(tmp_path)))
    names = [h[0] for h in ev["host"]]
    assert "bench.window" in names and "bench.solve" in names
    assert ev["devices"] == {}          # the CPU has no device plane
    assert trace.reduce(ev) is None


def test_device_planes():
    assert trace.is_device_plane("/device:TPU:3")
    assert not trace.is_device_plane("/device:CUSTOM:Megascale Trace")
    assert not trace.is_device_plane("/host:CPU")
    assert trace.stable_name(
        "%batched_fused_iteration_frow.3 = (f32[1,2]) custom-call(x)") == \
        "batched_fused_iteration_frow"


def test_byte_counts():
    assert bytecount.least_solve_bytes(20480, 20480, 4, 10) == \
        10 * 20480 * 20480 * 4
    assert bytecount.least_solve_bytes(10240, 40960, 2, 50) == \
        50 * 10240 * 40960 * 2


def _run(summary, facts, kind="TPU v5 lite"):
    return types.SimpleNamespace(
        trace_summary=summary, facts=facts,
        devices=[types.SimpleNamespace(device_kind=kind)])


def test_readers_compute_from_summary_and_facts():
    s = {"window_s": 10.0, "busy_s": 9.0, "busy_in": {"bench.solve": 8.19}}
    facts = {"solves": 10, "least_bytes_per_solve": 10 ** 9,
             "steps": 4, "step_s": 0.1}
    run = _run(s, facts)
    assert layers.idle_pct(run) == pytest.approx(10.0)
    # 1 GB per solve in 0.819 s of busy time is 1.22 GB/s of 819 GB/s
    assert layers.solve_roofline(run) == pytest.approx(100 / 819 * 1 / 0.819)
    assert layers.round_ms(run) == pytest.approx(25.0)


def test_readers_return_none_without_data_and_refuse_unknown_chips():
    empty = _run(None, {})
    for reader in (layers.idle_pct, layers.solve_roofline, layers.round_ms):
        assert reader(empty) is None
    s = {"window_s": 1.0, "busy_s": 1.0, "busy_in": {"bench.solve": 1.0}}
    with pytest.raises(KeyError):
        layers.solve_roofline(_run(s, {"solves": 1,
                                       "least_bytes_per_solve": 1},
                                   kind="TPU v9"))


@pytest.mark.parametrize("path", sorted(
    (REPO / "bench" / "metrics").glob("*.py")), ids=lambda p: p.stem)
def test_every_metric_file_has_a_reader(path):
    from bench.harness import load_module
    assert callable(load_module(path).read)
