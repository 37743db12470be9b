#!/usr/bin/env python3
"""Readings that the limits of a cell's output comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3
                               [--seconds S] [--first-seed N]

In one process, on the cell's chips and at the cell's own sizes, runs the
cell's driver once per seed with the program as configured, then once per
control seed with the control, the step that would tempt a later change:

- ``--control program``: the program with its coupling stored in bfloat16
  (``dtype`` of the configuration set to ``bfloat16``);
- ``--control reference``: the reference, computed with its coupling
  stored in bfloat16, put in the place of the program's solve entry
  (for the one-shot solve cells, where the program's own bfloat16 path
  does not run at the cell's size);
- ``--control short``: a fault, not a precision: the program's one-shot
  solve run for a fixed count one iteration below the count at which the
  reference stops at ``tol``; a solve that stops early reads faster.

Prints each compared number per seed, and the largest reading of the
program and the smallest of the control. A limit lies between the two.
``--seeds 0`` reads the control alone. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))
CONTROL_DTYPE = "bfloat16"


def _reference_kw(config: dict) -> dict:
    from bench import reference
    return dict(exponent=reference.fi(config["reg"], config["reg_m"]),
                tol=config["tol"], num_iters=config["num_iters"])


@contextlib.contextmanager
def _solve_replaced(make):
    """``ops.solve_fused`` replaced by ``make(original)`` for a while."""
    from repro.kernels import ops
    saved = ops.solve_fused
    ops.solve_fused = make(saved)
    try:
        yield
    finally:
        ops.solve_fused = saved


def reference_in_place(config: dict):
    """The bfloat16 reference in the place of the one-shot solve."""
    import jax.numpy as jnp
    from bench import reference

    def solve(K, a, b, *_, **__):
        P, colsum, _, _ = reference.solve(K, a, b, dtype=jnp.bfloat16,
                                          **_reference_kw(config))
        return P, colsum
    return _solve_replaced(lambda _: solve)


def one_iteration_short(config: dict):
    """The program's one-shot solve, stopped one iteration before the
    reference's ``tol`` stop on the same arrays."""
    import dataclasses
    from bench import reference

    def make(orig):
        def solve(K, a, b, cfg, **kw):
            iters = int(reference.solve(K, a, b,
                                        **_reference_kw(config))[2])
            return orig(K, a, b, dataclasses.replace(
                cfg, tol=None, num_iters=max(1, iters - 1)), **kw)
        return solve
    return _solve_replaced(make)


PATCHES = {"reference": reference_in_place, "short": one_iteration_short}


def readings(root, cell_name: str, seeds, seconds: float, control=None,
             platform: str = "tpu") -> list[dict]:
    """``{seed, correct, checks}`` of each seed's run of the cell."""
    from bench import harness

    cell = harness.load_cell(root, cell_name)
    config = (dict(cell.config, dtype=CONTROL_DTYPE) if control == "program"
              else cell.config)
    devices = harness.devices_for(cell.entry, platform)
    if devices is None:
        raise SystemExit(2)
    out = []
    for seed in seeds:
        run = cell.new_run(seed=seed, seconds=seconds, trace=False,
                           devices=devices, t_start=time.perf_counter(),
                           config=config)
        with (PATCHES[control](config) if control in PATCHES
              else contextlib.nullcontext()):
            cell.driver.run(run)
        rec = {"seed": seed, "control": control, "correct": run.correct,
               "checks": {n: v for n, v, _ in run.checks},
               "metrics": run.metrics, "setup_s": run.setup_s}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=1_000_003)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--control", choices=("program", "reference", "short"),
                   default="program")
    args = p.parse_args(argv)
    from repro.launch import compile_cache
    import os
    os.environ.pop(compile_cache.ENV, None)
    compile_cache.enable(ROOT)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    cseeds = [args.first_seed + 104729 * (i + 1)
              for i in range(args.control_seeds)]
    prog = readings(ROOT, args.workload, seeds, args.seconds)
    ctrl = readings(ROOT, args.workload, cseeds, args.seconds, args.control)
    for name in ctrl[0]["checks"]:
        lo = max((r["checks"][name] for r in prog), default=float("nan"))
        hi = min(r["checks"][name] for r in ctrl)
        print(f"{args.workload} {name}: program max {lo!r} over "
              f"{len(prog)} seeds, control min {hi!r} over {len(ctrl)} "
              f"seeds, ratio {hi / lo if lo else float('inf')!r}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
