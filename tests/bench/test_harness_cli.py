"""The benchmark's command: no result without the chip, for an unknown
cell, or in a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CMD = BENCH["command"]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable] + CMD[1:] + list(args)
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          env=env, timeout=300)


def _no_result(proc):
    return proc.returncode != 0 and not any(
        line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_no_accelerator_no_result(cell):
    proc = _run(REPO, "--workload", cell, "--seed", str(2**31 + 1),
                "--seconds", "1", "--trace", "0")
    assert _no_result(proc), proc.stdout
    assert "no result" in proc.stderr


def test_unknown_cell_no_result():
    proc = _run(REPO, "--workload", "no.such.cell", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert _no_result(proc)


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "--workload", "dense20k.solve", "--seed", "3",
                "--seconds", "1", "--trace", "0")
    assert _no_result(proc)
