"""The row-sharded gang on the streamed kernel tier, against the one-device
streamed solve and the benchmark's blocked reference, on four forced host
devices with the Pallas kernels in interpret mode, and the ``gang.solve``
span and counters of ``gang_solve_sharded``.

Each case runs tests/_gang_check.py in a fresh interpreter: XLA's device
count is fixed when JAX starts, so the main test process keeps its one
device. The tolerances and their reasons are in that file.
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
CASES = ["tol", "fixed", "drifts_cross_apart", "references_agree",
         "span_and_counters"]


@pytest.mark.parametrize("case", CASES)
def test_gang(case):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "_gang_check.py"), case],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert f"GANG_OK {case}" in proc.stdout
