"""The readers of the scheduler phases' idle time per round, on made-up
trace summaries and on a made-up trace reduced by ``bench.trace``."""
import types

import pytest

from bench import phases, trace


def _run(summary, facts):
    return types.SimpleNamespace(trace_summary=summary, facts=facts)


SUMMARY = {"window_s": 10.0, "busy_s": 0.5,
           "idle_gaps": [["serve.step", 4.0], ["serve.evict", 2.0],
                         ["serve.chunk", 1.5], ["host_idle", 0.5]]}


def test_idle_ms_per_round_is_the_label_over_the_rounds():
    run = _run(SUMMARY, {"steps": 40, "step_s": 9.0})
    assert phases.idle_ms_per_round(run, "serve.evict") == \
        pytest.approx(50.0)
    assert phases.chunk(run) == pytest.approx(37.5)
    assert phases.evict(run) == pytest.approx(50.0)


def test_an_absent_label_gives_none():
    run = _run(SUMMARY, {"steps": 40, "step_s": 9.0})
    assert phases.idle_ms_per_round(run, "serve.upkeep") is None
    for reader in (phases.evict_read, phases.admit, phases.admit_launch,
                   phases.upkeep, phases.points):
        assert reader(run) is None


@pytest.mark.parametrize("facts", [{}, {"steps": 0, "step_s": 0.0}])
def test_no_rounds_or_no_summary_gives_none(facts):
    assert phases.idle_ms_per_round(_run(SUMMARY, facts), "serve.evict") \
        is None
    assert phases.idle_ms_per_round(_run(None, {"steps": 4}),
                                    "serve.evict") is None


def test_a_phase_reads_its_own_idle_time_its_children_excluded():
    # one round 5-95 in a window of 100 ns: evict 10-60 holds a lane
    # read 20-50, the device is busy 70-80 inside the chunk 60-90
    events = {"devices": {"/device:TPU:0": [["%fusion.1 = f32[] f()", 70,
                                             10]]},
              "host": [["bench.window", 0, 100], ["serve.step", 5, 90],
                       ["serve.evict", 10, 50], ["serve.evict.read", 20, 30],
                       ["serve.chunk", 60, 30]]}
    run = _run(trace.reduce(events), {"steps": 1})
    ns_ms = 1e-9 * 1000.0
    assert phases.evict(run) == pytest.approx(20 * ns_ms)
    assert phases.evict_read(run) == pytest.approx(30 * ns_ms)
    assert phases.chunk(run) == pytest.approx(20 * ns_ms)
    assert phases.idle_ms_per_round(run, "serve.step") == \
        pytest.approx(10 * ns_ms)
    assert phases.idle_ms_per_round(run, "host_idle") == \
        pytest.approx(10 * ns_ms)
