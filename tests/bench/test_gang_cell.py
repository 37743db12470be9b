"""The four-chip gang cell: its files, a small copy of it run end to end on
four forced host devices, and the reader of its collective's exposed
share, on a trace recorded on the chip and on small made-up traces."""
import collections
import json
import os
import pathlib
import subprocess
import sys

import pytest

from conftest import REPO, load_bench
from bench import mesh, trace

CELL = "gang80k.solve"
HERE = pathlib.Path(__file__).resolve().parent


def test_cell_files_name_what_exists():
    bench = load_bench()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "back_to_back"
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    data = json.loads((REPO / config["file"]).read_text())
    assert data["reduced"] == config["reduced"] == []
    assert data["data"]["M"] == data["data"]["N"] == 81920
    bdir = REPO / "bench"
    assert (bdir / "traffic" / f"{cell['traffic']}.json").is_file()
    wl = json.loads((bdir / "workloads" / f"{CELL}.json").read_text())
    assert (bdir / "drivers" / f"{wl['driver']}.py").is_file()
    from repro.core import distributed
    assert callable(getattr(distributed, data["entry"]))
    metrics = [m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]]
    assert sorted(metrics) == ["collective_exposed_pct.gang",
                               "device_idle_pct.gang", "gang_roofline"]
    for name in metrics:
        assert (bdir / "metrics" / f"{name}.py").is_file()
    e2e = [m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])]
    assert sorted(e2e) == ["peak_hbm_gb", "setup_s", "solve_s"]


@pytest.fixture(scope="module")
def small_gang():
    """The small copy's sound run, traced run, control and fault."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, str(HERE / "_gang_cell_check.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("GANG_CELL ")]
    return json.loads(line[-1][len("GANG_CELL "):])


def test_small_gang_runs_correct(small_gang):
    rc, res = small_gang["sound"]
    assert rc == 0 and res["correct"], res
    assert res["device"]["count"] == 4
    assert {"solve_s", "peak_hbm_gb", "setup_s"} <= set(res["metrics"])
    assert res["compared"]["iters_off"]["value"] == 0
    rc, res = small_gang["traced"]
    assert rc == 0 and res["correct"], res
    assert "setup_s" not in res["metrics"]


@pytest.mark.parametrize("kind", ["control", "fault"])
def test_control_and_fault_are_not_correct(small_gang, kind):
    recs = small_gang[kind]
    assert len(recs) == 2 and not any(r["correct"] for r in recs)
    for r in recs:
        checks = r["checks"]
        if kind == "fault":      # one iteration short
            assert checks["iters_off"] == 1, r
        assert checks["coupling_err"] > 1e-5, r


def _events(devices, spans):
    return {"devices": devices, "host": spans}


def test_exposed_share_counts_only_collective_time_no_op_covers():
    ev = _events(
        {"/device:TPU:0": [["%fusion.1 = f", 0, 40],
                           ["%all-reduce.2 = f32[1,8]", 30, 20],   # 40-50
                           ["%while.3 = w", 0, 100],               # holds all
                           ["%all-reduce-start.4 = f", 80, 10]],  # 80-90
         "/device:TPU:1": [["%psum-ish.1 = f", 0, 100],
                           ["%all-reduce.2 = f32[1]", 10, 10]]},   # covered
        [["bench.window", 0, 200], ["gang.solve", 0, 85],
         ["gang.solve", 85, 15]])
    # device 0: 40-50 and 80-90 exposed, all inside the spans (0-100)
    assert mesh.exposed_collective_pct(ev) == pytest.approx(20.0)


def test_exposed_share_needs_the_program_span():
    ev = _events({"/device:TPU:0": [["%all-reduce.1 = f", 0, 10]]},
                 [["bench.window", 0, 100]])
    assert mesh.exposed_collective_pct(ev) is None


def test_subtract_intervals():
    assert mesh.subtract([(0, 10), (20, 30)], [(2, 4), (5, 25)]) == [
        (0, 2), (4, 5), (25, 30)]
    assert mesh.subtract([(0, 10)], []) == [(0, 10)]
    assert mesh.subtract([(0, 10)], [(0, 10)]) == []


def test_least_bytes_per_device():
    assert mesh.least_bytes_per_device(81920, 81920, 4, 4, 12) == (
        12 * 20480 * 81920 * 4)


def _exposed_by_sweep(events: dict) -> float:
    """The reader's number, counted another way: cut the time line at
    every start and end, and add up the pieces inside a ``gang.solve``
    span in which a collective runs and no other op (nor container) does,
    per device."""
    spans = [(s, s + d) for n, s, d in events["host"] if n == mesh.GANG_SPAN]
    inside = trace.length(trace.merge(spans))
    shares = []
    for evs in events["devices"].values():
        coll = [(s, s + d) for n, s, d in evs if mesh.is_collective(n)]
        other = [(s, s + d) for n, s, d in evs if not mesh.is_collective(n)
                 and trace.stable_name(n) not in trace.CONTAINERS]
        cuts = sorted({t for iv in spans + coll + other for t in iv})
        total = 0
        for lo, hi in zip(cuts, cuts[1:]):
            def on(ivs):
                return any(s <= lo and hi <= e for s, e in ivs)
            if on(spans) and on(coll) and not on(other):
                total += hi - lo
        shares.append(total / inside)
    return 100.0 * max(shares)


def test_exposed_share_on_a_v5e_gang_trace():
    """Three 8192² gang solves of 13 iterations recorded on a 2x2 v5e
    host. On each device the all-reduces are named for their primitives
    (``psum``, ``pmax``): one of the column sums after the first pass and
    after every iteration, one of the drift per iteration. The reader
    finds them all, and its share agrees with a plain sweep."""
    rec = json.loads((HERE / "data" / "trace_v5e_gang.json").read_text())
    ev = rec["events"]
    assert len(ev["devices"]) == 4
    assert sum(n == mesh.GANG_SPAN for n, _, _ in ev["host"]) == 3
    for evs in ev["devices"].values():
        shapes = collections.Counter(
            n.split(" = ")[1].split("{")[0] for n, _, _ in evs
            if mesh.is_collective(n))
        assert shapes == {"f32[1,8192]": 3 * 14, "f32[1]": 3 * 13}, shapes
    pct = mesh.exposed_collective_pct(ev)
    assert 0.0 < pct < 100.0
    assert pct == pytest.approx(_exposed_by_sweep(ev), rel=1e-9)


def test_collectives_are_found_by_opcode():
    assert mesh.is_collective("%psum.21 = f32[1,8192]{1,0:T(1,128)S(1)} "
                              "all-reduce(f32[1,8192]{1,0} %bitcast.47)")
    assert mesh.is_collective("%all-reduce-start.4 = f32[8] "
                              "all-reduce-start(f32[8] %x)")
    assert not mesh.is_collective("%broadcast_select_fusion.3 = f32[1,8192]"
                                  "{1,0} fusion(f32[1,8192]{1,0} %psum.21)")
