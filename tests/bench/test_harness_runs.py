"""Small cells that exist only in the tests, run end to end on the CPU:
sound runs are correct, and the control and each fault planted under
the timed path turn ``correct`` false."""
import pytest

import faults
from conftest import run_cell

ONE_CHIP = ["tiny.solve", "tiny.closed"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_small_cell_runs_correct(small_root, cell):
    rc, res = run_cell(small_root, cell)
    assert rc == 0 and res["correct"], res
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert res["device"]["platform"] == "cpu"
    for c in res["compared"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", ["tiny.closed"])
def test_traced_run_reports_the_scheduler_round(small_root, cell):
    rc, res = run_cell(small_root, cell, trace=1)
    assert rc == 0 and res["correct"]
    assert res["metrics"]["round_ms.small"]["value"] > 0
    assert "setup_s" not in res["metrics"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_in_bfloat16_is_not_correct(small_root, cell):
    from bench.calibrate import readings
    recs = readings(small_root, cell, [7, 2**31 + 3], 0.5, control="program",
                    platform="cpu")
    assert recs and not any(r["correct"] for r in recs)


@pytest.mark.parametrize("cell", ["tiny.solve"])
def test_reference_in_bfloat16_in_place_is_not_correct(small_root, cell):
    from bench.calibrate import readings
    recs = readings(small_root, cell, [7, 2**31 + 3], 0.5,
                    control="reference", platform="cpu")
    assert recs and not any(r["correct"] for r in recs)


@pytest.mark.parametrize("cell", ["tiny.solve"])
def test_solve_one_iteration_short_is_not_correct(small_root, cell):
    """A solve stopped one iteration before the tol stop reads faster;
    the output comparison has to catch it."""
    from bench.calibrate import readings
    recs = readings(small_root, cell, [7, 2**31 + 3], 0.5, control="short",
                    platform="cpu")
    assert recs and not any(r["correct"] for r in recs)
    for r in recs:
        assert all(v > 0 for v in r["checks"].values()), r


FAULTS = [
    ("tiny.solve", faults.solve_unchanged),
    ("tiny.solve", faults.solve_altered),
    ("tiny.closed", faults.chunk_unchanged),
    ("tiny.closed", faults.chunk_half_the_lanes),
    ("tiny.closed", faults.answers_altered),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_under_the_timed_path_is_not_correct(small_root, cell, fault):
    with fault():
        rc, res = run_cell(small_root, cell)
    assert rc == 0 and res["correct"] is False, res
