"""Subprocess body of tests/bench/test_gang_cell.py: a small copy of the
``gang80k.solve`` cell, run end to end on four forced host devices.

XLA's device count is fixed when JAX starts, so the flag is set here,
before JAX is imported, in a fresh interpreter. Prints one JSON object:
the result line of a sound run, of a traced run, and the calibration
readings of the control and of the fault.
"""
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
import pathlib  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from conftest import make_root, run_cell  # noqa: E402

CELL, SMALL = "gang80k.solve", "tiny.gang"
SIZE = {"M": 512, "N": 384, "mass_b": 1.2}


def gang_root(dest: pathlib.Path) -> pathlib.Path:
    """``make_root``'s copy of the benchmark, plus a small gang cell that
    reports what ``gang80k.solve`` reports."""
    root = make_root(dest)
    bdir = root / "bench"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    real = {w["name"]: w for w in bench["workloads"]}[CELL]
    config = json.loads(
        (bdir / "configs" / f"{real['config']}.json").read_text())
    config["data"] = SIZE
    (bdir / "configs" / "tiny-gang.json").write_text(json.dumps(config))
    (bdir / "workloads" / f"{SMALL}.json").write_text(
        (bdir / "workloads" / f"{CELL}.json").read_text())
    bench["configs"].append({
        "name": "tiny-gang", "reduced": [],
        "source": "https://arxiv.org/abs/2412.11079",
        "file": "bench/configs/tiny-gang.json",
        "why": "a small copy for the CPU tests"})
    bench["workloads"].append(dict(real, name=SMALL, config="tiny-gang",
                                   why="exists only in the harness tests"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(SMALL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def main():
    import jax
    assert jax.device_count() == 4, jax.device_count()
    from bench.calibrate_gang import readings
    with tempfile.TemporaryDirectory() as tmp:
        root = gang_root(pathlib.Path(tmp))
        out = {"sound": run_cell(root, SMALL),
               "traced": run_cell(root, SMALL, trace=1)}
        for kind in ("control", "fault"):
            out[kind] = readings(root, SMALL, [7, 2**31 + 3], 0.3, kind,
                                 platform="cpu")
    print("GANG_CELL " + json.dumps(out))


if __name__ == "__main__":
    main()
