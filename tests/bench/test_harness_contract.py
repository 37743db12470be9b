"""BENCHMARK.json and the files it names: every name, unit and file keeps
to the benchmark's contract, and every cell reports what it must."""
import json
import re

import pytest

from conftest import REPO, load_bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = load_bench()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)


def test_command_and_paths_stay_inside_the_benchmark():
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (REPO / p).is_dir()
    files = [w for w in cmd if "/" in w]
    assert files and all(
        any(f.startswith(p + "/") for p in BENCH["paths"]) for f in files)


def test_names_units_and_keys():
    names = [c["name"] for c in BENCH["configs"]] + list(CELLS) + list(E2E) \
        + [m["name"] for m in BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(c["name"] for c in BENCH["configs"])) == len(
        BENCH["configs"])
    metric_names = list(E2E) + [m["name"] for m in BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in CELLS.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and one_line(w["why"])
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and one_line(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in CELLS.values())
    assert four <= max(1, len(CELLS) // 2)


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for cell in CELLS:
        e2e = [m for m in E2E.values() if cell in m.get("workloads", [cell])]
        layer = [m for m in BENCH["per_layer"] if cell in m["workloads"]]
        assert "setup_s" in {m["name"] for m in e2e}
        assert len(e2e) >= 2 and layer, cell


def test_per_layer_metrics_list_cells_that_report_what_they_move():
    for m in BENCH["per_layer"]:
        moves = E2E[m["moves"]]
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in moves.get("workloads", [cell]), (m["name"], cell)


def test_metrics_with_a_shared_layer_spell_it_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers <= {"kernels", "device", "mesh", "serving scheduler"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_exist_and_name_what_exists(cell):
    w = CELLS[cell]
    bdir = REPO / "bench"
    wl = json.loads((bdir / "workloads" / f"{cell}.json").read_text())
    assert (bdir / "drivers" / f"{wl['driver']}.py").is_file()
    assert wl["limits"] and all(v >= 0 for v in wl["limits"].values())
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert (bdir / "traffic" / f"{w['traffic']}.json").is_file()
    for m in BENCH["per_layer"]:
        assert (bdir / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_files_hold_the_configuration(config):
    c = {c["name"]: c for c in BENCH["configs"]}[config]
    path = REPO / c["file"]
    assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    data = json.loads(path.read_text())
    for key in ("source", "reg", "reg_m", "tol", "num_iters", "dtype",
                "assumed", "reduced"):
        assert key in data, key
    assert data["reduced"] == c["reduced"]
    files = [x["file"] for x in BENCH["configs"]]
    assert len(set(files)) == len(files)


def test_a_full_check_fits_its_time_with_24_cells():
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
