#!/usr/bin/env python3
"""Readings that the limits of the gang cell's output comparison are set
from.

    python3 bench/calibrate_gang.py --workload gang80k.solve --seeds 12
                                    --control-seeds 3 --fault-seeds 3
                                    [--seconds S] [--first-seed N]

In one process, on the cell's chips and at the cell's own sizes, runs the
cell's driver (``bench/drivers/gang.py``) once per seed with the program
as configured, then once per control seed and once per fault seed with
the program's gang entry replaced:

- ``control``: the reference computed with K rounded once to bfloat16
  (``bench.reference_blocked.solve(k_dtype=bfloat16)``), its coupling
  laid out on the mesh as the gang's would be: the same solve in the
  nearest precision below the configuration's;
- ``fault``: the program's gang run for a fixed count one iteration below
  the count at which the reference stops at ``tol``; a solve that stops
  early reads faster.

Prints each compared number per seed, and per number the largest reading
of the program and the smallest of the control and of the fault. A limit
lies between the two. The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


@contextlib.contextmanager
def _replaced(obj, name: str, make):
    saved = getattr(obj, name)
    setattr(obj, name, make(saved))
    try:
        yield
    finally:
        setattr(obj, name, saved)


@contextlib.contextmanager
def _gang_replaced(driver, config: dict, make_solve):
    """The driver's points kept as it draws them, and the program's gang
    entry replaced by ``make_solve(original, kept)`` for a while."""
    from repro.core import distributed

    kept = {}

    def keep_points(orig):
        def points(seed, d):
            kept["points"] = orig(seed, d)
            return kept["points"]
        return points

    with _replaced(driver, "points", keep_points), _replaced(
            distributed, config["entry"],
            lambda orig: make_solve(orig, kept)):
        yield


def _reference(driver, config: dict, kept: dict, k_dtype):
    from bench import reference_blocked
    x = kept["points"][0]
    return reference_blocked.solve(
        *kept["points"], block=math.gcd(x.shape[0], driver.BLOCK),
        k_dtype=k_dtype, **driver.reference_kw(config))


def control(driver, config: dict):
    """The bfloat16-K reference in the place of the gang entry."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import reference_blocked

    def make_solve(orig, kept):
        def solve(mesh, axis, K, a, b, cfg, **_):
            if "out" not in kept:
                u, v, colsum, iters, _ = _reference(driver, config, kept,
                                                    jnp.bfloat16)
                rows, rep = (NamedSharding(mesh, P(axis)),
                             NamedSharding(mesh, P()))
                A = jax.jit(lambda x, y, u, v: reference_blocked.coupling(
                    x, y, u, v, reg=config["reg"], k_dtype=jnp.bfloat16),
                    out_shardings=NamedSharding(mesh, P(axis, None)))(
                    jax.device_put(kept["points"][0], rows),
                    jax.device_put(kept["points"][1], rep),
                    jax.device_put(u, rows), jax.device_put(v, rep))
                kept["out"] = (A, jax.device_put(colsum, rep), int(iters))
            return kept["out"]
        return solve
    return _gang_replaced(driver, config, make_solve)


def fault(driver, config: dict):
    """The gang stopped one iteration before the reference's ``tol``
    stop on the same points."""
    import jax.numpy as jnp

    def make_solve(orig, kept):
        def solve(mesh, axis, K, a, b, cfg, **kw):
            if "iters" not in kept:
                kept["iters"] = int(_reference(driver, config, kept,
                                               jnp.float32)[3])
            return orig(mesh, axis, K, a, b, dataclasses.replace(
                cfg, tol=None, num_iters=max(1, kept["iters"] - 1)), **kw)
        return solve
    return _gang_replaced(driver, config, make_solve)


PATCHES = {"control": control, "fault": fault}


def readings(root, cell_name: str, seeds, seconds: float, kind=None,
             platform: str = "tpu") -> list[dict]:
    """``{seed, kind, correct, checks}`` of each seed's run of the cell."""
    from bench import harness

    cell = harness.load_cell(root, cell_name)
    devices = harness.devices_for(cell.entry, platform)
    if devices is None:
        raise SystemExit(2)
    out = []
    for seed in seeds:
        run = cell.new_run(seed=seed, seconds=seconds, trace=False,
                           devices=devices, t_start=time.perf_counter())
        with (PATCHES[kind](cell.driver, cell.config) if kind
              else contextlib.nullcontext()):
            cell.driver.run(run)
        rec = {"seed": seed, "kind": kind, "correct": run.correct,
               "checks": {n: v for n, v, _ in run.checks},
               "iters": run.facts.get("iters"), "metrics": run.metrics,
               "memory_peak_bytes": run.memory_peak_bytes}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def summary(name: str, prog, others: dict) -> list[str]:
    """Per compared number: the program's largest reading and each other
    kind's smallest."""
    lines = []
    for check in prog[0]["checks"]:
        hi = max(r["checks"][check] for r in prog)
        lows = ", ".join(
            f"{kind} min {min(r['checks'][check] for r in recs)!r}"
            for kind, recs in others.items() if recs)
        lines.append(f"{name} {check}: program max {hi!r} over {len(prog)} "
                     f"seeds; {lows}")
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--fault-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=3_000_017)
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args(argv)
    from repro.launch import compile_cache
    import os
    os.environ.pop(compile_cache.ENV, None)
    compile_cache.enable(ROOT)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    cseeds = [args.first_seed + 104729 * (i + 1)
              for i in range(args.control_seeds)]
    fseeds = [args.first_seed + 130363 * (i + 1)
              for i in range(args.fault_seeds)]
    prog = readings(ROOT, args.workload, seeds, args.seconds)
    others = {kind: readings(ROOT, args.workload, s, args.seconds, kind)
              for kind, s in (("control", cseeds), ("fault", fseeds))}
    for line in summary(args.workload, prog, others):
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
