"""Fixtures of the benchmark harness tests: a copy of the benchmark with
small cells of its own, which exist only in the tests and run on the CPU.

The small cells reuse the drivers, the generator, the reference and the
metric readers as they are, with new configuration, mix and workload
files and new entries in a copy of ``BENCHMARK.json``: adding a cell
takes data only.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (REPO / "src", REPO):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

KINDS = [{"kind": "dense", "count": 2}, {"kind": "points", "d": 3, "count": 1},
         {"kind": "points", "d": 32, "count": 1}]

# Each small cell stands in for a cell of the benchmark: it takes that
# cell's configuration with smaller sizes, and reports what it reports.
SMALL_CELLS = {
    "tiny.solve": {"for": "dense20k.solve", "config": "tiny-dense",
                   "change": {"data": {"M": 200, "N": 320, "mass_b": 1.2}}},
    "tiny.closed": {"for": "service.small", "config": "tiny-service",
                    "change": {}, "mix": ("tiny-closed", {
                        "loop": "closed", "sides": [64, 100],
                        "kinds": KINDS, "distinct_units": 2,
                        "clients": 32})},
}


def make_root(dest: pathlib.Path) -> pathlib.Path:
    """A checkout-like root: ``BENCHMARK.json`` and ``bench/`` copied, plus
    the small cells' files and entries."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bdir = dest / "bench"
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    for name, small in SMALL_CELLS.items():
        real = cells[small["for"]]
        config = json.loads(
            (bdir / "configs" / f"{real['config']}.json").read_text())
        config.update(small["change"])
        (bdir / "configs" / f"{small['config']}.json").write_text(
            json.dumps(config))
        mix = real["traffic"]
        if "mix" in small:
            mix, mix_data = small["mix"]
            (bdir / "traffic" / f"{mix}.json").write_text(
                json.dumps(mix_data))
        wl = json.loads(
            (bdir / "workloads" / f"{real['name']}.json").read_text())
        wl["drain_s"] = 2
        (bdir / "workloads" / f"{name}.json").write_text(json.dumps(wl))
        if small["config"] not in configs:
            configs[small["config"]] = {
                "name": small["config"], "reduced": [],
                "source": "https://arxiv.org/abs/2412.11079",
                "file": f"bench/configs/{small['config']}.json",
                "why": "a small copy for the CPU tests"}
            bench["configs"].append(configs[small["config"]])
        bench["workloads"].append(dict(real, name=name, config=small[
            "config"], traffic=mix, why="exists only in the harness tests"))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real["name"] in m.get("workloads", []):
                m["workloads"].append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dest


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


def run_cell(root, cell, *, seed=2**31 + 11, seconds=0.5, trace=0):
    """Run a cell in this process on the CPU; returns (rc, result line)."""
    import io
    import contextlib
    from bench import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, platform="cpu", cache=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 else None)


def load_bench() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())
