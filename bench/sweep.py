#!/usr/bin/env python3
"""Load sweep of a closed-loop service cell, to find where it saturates.

    python3 bench/sweep.py --workload service.small --clients 24,48,96 [--seconds S]

In one process on the cell's chip: runs the cell's driver once per
client count (``clients`` of the mix replaced), each on its own seed, and
prints one JSON line per point with the cell's end-to-end metric. The
cell's clients are set where the requests completed per second stop
growing. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--clients", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--first-seed", type=int, default=2_000_003)
    args = p.parse_args(argv)
    import os
    from bench import harness
    from repro.launch import compile_cache
    os.environ.pop(compile_cache.ENV, None)
    compile_cache.enable(ROOT)

    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.devices_for(cell.entry, "tpu")
    if devices is None:
        return 2
    for k, v in enumerate(int(x) for x in args.clients.split(",")):
        run = cell.new_run(seed=args.first_seed + 7919 * k,
                           seconds=args.seconds, trace=False, devices=devices,
                           t_start=time.perf_counter(),
                           traffic=dict(cell.traffic, clients=v))
        cell.driver.run(run)
        print(json.dumps({"clients": v, "seconds": args.seconds,
                          "compiles": run.compiles.count,
                          "setup_s": run.setup_s, "correct": run.correct,
                          "attempted": run.attempted, "failed": run.failed,
                          **run.metrics, **run.facts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
